"""The GLM-4.7-Flash configuration of the transfer-stream ranker
(``models/stream.py``: latent attention (MLA), a leading dense layer, 64
experts chosen by sigmoid score plus a selection bias and weighted by the
score, renormalised and times 1.8, an ungated shared expert) held to its
plain float32 reference (``benchmark/reference/glm-4-7-flash-t8.py``) at
tiny widths on the CPU with seeded weights: loss and gradients leaf by
leaf, the first dispatch's AdamW step and the selection bias's rule
through the trainer, the eight shares of a layer against the uncut layer,
the latent attention against a per-head einsum of its equations, and what
the step counts through the trainer's ledger.  Values, gradients and
counts, never a time."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, run as bench
from dragonfly2_tpu.models import build_ranker, stream
from dragonfly2_tpu.ops import grouped_matmul, slot_rows
from dragonfly2_tpu.trainer import metrics as trainer_metrics
from dragonfly2_tpu.trainer.online_graph import OnlineGraphConfig, OnlineGraphTrainer
from dragonfly2_tpu.trainer.train import TrainConfig, _huber
from tests._glm_sizes import (  # noqa: F401 — fixtures
    B, HOP_DIM, L, M, N, NAME, ROWS, _records, cfg, hop, ref,
)
from tests.test_stream_ranker import ring  # noqa: F401 — fixture
from tests.test_stream_reference import _flat, _leaf_gaps

TRAIN = {"learning_rate": 3e-4, "weight_decay": 1e-4, "warmup_steps": 100}
EXPERT_LAYERS = range(M["first_k_dense_replace"], M["num_hidden_layers"])


@pytest.fixture(scope="module")
def weights(ref, cfg, hop):
    """(the flax module's parameters, the reference's own from the same
    key): equal bit for bit, each made by its own code."""
    key = jax.random.PRNGKey(3)
    ids = jnp.zeros((2,), jnp.int32)
    ours = stream.StreamRanker(cfg).init(key, hop, None, ids, ids)["params"]
    return ours, ref.init_params(key, M, HOP_DIM, N)


@pytest.fixture(scope="module")
def bias():
    """A selection bias [expert layers, experts] large enough to change
    the choice of many tokens (the step's rule moves it by 0.001)."""
    return np.random.default_rng(7).normal(size=(len(EXPERT_LAYERS), M["n_routed_experts"])).astype(np.float32) * 0.05


def _program_loss_and_grads(cfg, params, bias, hop, src, dst, y):
    model = stream.StreamRanker(cfg)
    qef = stream.previous_target(jnp.asarray(dst), jnp.asarray(y), cfg.positions)
    carried = {stream.SELECTION_BIAS: {f"layer_{i}": jnp.asarray(b) for i, b in zip(EXPERT_LAYERS, bias)}}

    def loss(p):
        pred = model.apply({"params": p, **carried}, hop, None, jnp.asarray(src), jnp.asarray(dst), qef, train=True)
        return _huber(pred, jnp.asarray(y))

    with jax.default_matmul_precision("highest"):
        got, grads = jax.jit(jax.value_and_grad(loss))(params)
    return float(got), _flat(grads)


def _reference_loss_and_grads(ref, params, bias, hop, src, dst, y, variant="f32"):
    s, d, t = (a.reshape(-1, L) for a in (src, dst, y))
    prev = ref.previous_targets(d, t, M)
    table = ref.standard_table(hop)

    def loss(p):
        total = 0.0
        for r in range(s.shape[0]):
            pred, _ = ref.row_predictions(p, table, s[r], d[r], jnp.asarray(prev[r]), jnp.asarray(bias), M, variant)
            total = total + ref.C.huber_sum(pred, t[r])
        return total / s.size

    with jax.default_matmul_precision("highest"):
        got, grads = jax.jit(jax.value_and_grad(loss))(params)
    return float(got), {k: np.asarray(v) for k, v in grads.items()}


@pytest.fixture(scope="module")
def theirs(ref, hop, weights, bias):
    return _reference_loss_and_grads(ref, weights[1], bias, hop, *_records())


@pytest.fixture(scope="module")
def ours(cfg, hop, weights, bias):
    return _program_loss_and_grads(cfg, weights[0], bias, hop, *_records())


# float32 against float32: two orders of summation of the same products (an
# online against a whole softmax, sorted slots against a loop over experts,
# a head's dims rope first against rope last); a leaf reads 4e-7 at worst.
LOSS_TOL, LEAF_TOL = 1e-6, 1e-5
LEAVES = ["embed/embedding"] + [
    name for name, _, _ in bench.load_module("reference", NAME).parameter_list(M, HOP_DIM, N)
]


def test_reference_draws_the_programs_weights_bit_for_bit(weights):
    ours, theirs = _flat(weights[0]), weights[1]
    assert set(ours) == set(theirs) == set(LEAVES)
    for k in ours:
        np.testing.assert_array_equal(ours[k], np.asarray(theirs[k]), err_msg=k)


def test_loss_matches_the_reference(ours, theirs):
    assert abs(ours[0] - theirs[0]) <= LOSS_TOL * abs(theirs[0]), (ours[0], theirs[0])


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(ours, theirs, leaf):
    assert set(ours[1]) == set(theirs[1]) == set(LEAVES)
    assert np.linalg.norm(theirs[1][leaf]) > 0, "a leaf the loss does not reach is not compared"
    assert _leaf_gaps(ours[1], theirs[1])[leaf] <= LEAF_TOL


def _without_the_shared_key(monkeypatch):
    """The shared rope key's part of every score left out: the one
    ``_rope`` call on a single head (the key's) gives noughts."""
    turn = stream._rope
    monkeypatch.setattr(stream, "_rope", lambda x, *a: turn(x, *a) * (x.shape[-2] != 1))


LEFT_OUT = {
    "the shared rope key": lambda c, mp: (_without_the_shared_key(mp), c)[1],
    "the sigmoid scores": lambda c, mp: dataclasses.replace(c, scoring_func="softmax"),
    "the selection bias": lambda c, mp: dataclasses.replace(c, selection_bias_rate=0.0),
    "the 1.8 scale": lambda c, mp: dataclasses.replace(c, routed_scaling_factor=1.0),
}


@pytest.mark.parametrize("what", sorted(LEFT_OUT))
def test_the_comparison_fails_with_one_part_of_the_layer_left_out(cfg, hop, weights, bias, theirs, what, monkeypatch):
    """The tolerances see each thing this configuration brought: a program
    without it (the same parameters, another function) is not the
    reference's by a hundred times the leaves' tolerance."""
    changed = LEFT_OUT[what](cfg, monkeypatch)
    _, grads = _program_loss_and_grads(changed, weights[0], bias, hop, *_records())
    gaps = _leaf_gaps(grads, theirs[1])
    assert max(gaps.values()) > 100 * LEAF_TOL, (what, sorted(gaps.items(), key=lambda kv: -kv[1])[:5])


def test_a_reference_in_bfloat16_fails_the_same_tolerances(ref, hop, weights, bias, theirs):
    _, grads = _reference_loss_and_grads(ref, weights[1], bias, hop, *_records(), ref.CONTROL_BF16)
    failed = [k for k, v in _leaf_gaps(grads, theirs[1]).items() if v > LEAF_TOL]
    assert len(failed) > len(LEAVES) // 2


# -- the latent attention against its equations, head by head ---------------------------------


def test_latent_attention_is_the_per_head_einsum_of_its_equations(cfg):
    """Every head's q and k made whole from the latents, the rope part of
    k one head's for all, scores over the causal part of the query's
    segment: in float64, one head at a time, against the program's
    blockwise form (a head's dims held rope first there)."""
    rng = np.random.default_rng(21)
    h, nope, rope, dv = 4, 6, 4, 10
    w = lambda *s: rng.normal(size=s) * 0.3
    p = {
        "w_qa": w(32, 12), "q_norm": w(12), "w_qb": w(12, h * (nope + rope)), "w_kva": w(32, 8 + rope),
        "kv_norm": w(8), "w_kvb": w(8, h * (nope + dv)), "w_o": w(h * dv, 32),
    }
    x = rng.normal(size=(2, L, 32))
    dst = _records(3)[1].reshape(-1, L)[:2]
    _, seg, _ = stream.segments(jnp.asarray(dst.reshape(-1)), L)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(stream.latent_attention(
            jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), p), jnp.asarray(x, jnp.float32),
            seg, cfg, stream.Mixer(stream.ATTENTION),
        ))

    def norm(a, g):
        return a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-5) * (1 + g)

    def turn(a):                                  # [L, rope], the halves convention
        inv = 1.0 / (1e6 ** (np.arange(0, rope, 2) / rope))
        ang = np.arange(L)[:, None] * inv[None]
        cos, sin = np.cos(np.concatenate([ang, ang], -1)), np.sin(np.concatenate([ang, ang], -1))
        return a * cos + np.concatenate([-a[:, rope // 2:], a[:, : rope // 2]], -1) * sin

    seg = np.asarray(seg)
    want = np.zeros_like(got, dtype=np.float64)
    for r in range(2):
        c_q = norm(x[r] @ p["w_qa"], p["q_norm"])
        a = x[r] @ p["w_kva"]
        kv = norm(a[:, :8], p["kv_norm"]) @ p["w_kvb"]
        k_pe = turn(a[:, 8:])
        ok = (seg[r][:, None] == seg[r][None, :]) & np.tril(np.ones((L, L), bool))
        heads = []
        for i in range(h):
            q_i = (c_q @ p["w_qb"])[:, i * (nope + rope):(i + 1) * (nope + rope)]
            kv_i = kv[:, i * (nope + dv):(i + 1) * (nope + dv)]
            q_i = np.concatenate([q_i[:, :nope], turn(q_i[:, nope:])], -1)
            k_i = np.concatenate([kv_i[:, :nope], k_pe], -1)
            s = np.where(ok, np.einsum("qd,kd->qk", q_i, k_i) / np.sqrt(nope + rope), -np.inf)
            s = np.exp(s - s.max(-1, keepdims=True))
            heads.append((s / s.sum(-1, keepdims=True)) @ kv_i[:, nope:])
        want[r] = np.concatenate(heads, -1) @ p["w_o"]
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4 * np.abs(want).max())


def test_a_v_head_unlike_the_qk_head_is_refused(cfg):
    bad = dataclasses.replace(cfg, v_head_dim=cfg.v_head_dim + 2)
    with pytest.raises(ValueError, match="v_head_dim"):
        stream.latent_attention({}, jnp.zeros((1, L, 32)), jnp.zeros((1, L), jnp.int32), bad, stream.Mixer())


# -- the router ---------------------------------------------------------------------------------


def test_the_router_chooses_by_score_and_bias_and_weighs_by_score(cfg):
    """The four largest of sigmoid(logit) + b, weighted by sigmoid(logit)
    renormalised over the four and times 1.8: the weights of a token sum to
    1.8 and no gradient reaches the bias."""
    rng = np.random.default_rng(31)
    x = jnp.asarray(rng.normal(size=(B, 32)).astype(np.float32))
    p = {"router": jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32) * 0.3),
         "bias": jnp.asarray(rng.normal(size=(16,)).astype(np.float32) * 0.3)}
    with jax.default_matmul_precision("highest"):
        top_w, top_i = stream.route(p, x, cfg)
        grad = jax.grad(lambda b: stream.route({**p, "bias": b}, x, cfg)[0].sum())(p["bias"])
    s = 1 / (1 + np.exp(-np.asarray(x, np.float64) @ np.asarray(p["router"], np.float64)))
    pick = np.argsort(-(s + np.asarray(p["bias"])), axis=1)[:, :4]
    np.testing.assert_array_equal(np.sort(np.asarray(top_i), 1), np.sort(pick, 1))
    g = np.take_along_axis(s, np.asarray(top_i), 1)
    np.testing.assert_allclose(top_w, 1.8 * g / g.sum(1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(top_w).sum(1), 1.8, rtol=1e-5)
    assert not np.asarray(grad).any()
    # chosen by score alone, another set for many tokens
    assert (np.sort(np.asarray(stream.route({"router": p["router"]}, x, cfg)[1]), 1) != np.sort(pick, 1)).any(1).mean() > 0.2


# -- the expert layer's share ------------------------------------------------------------------


def _expert_weights(seed):
    rng = np.random.default_rng(seed)
    d, e, f = 32, M["n_routed_experts"], M["moe_intermediate_size"]
    w = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32) * 0.3)
    return {
        "router": w(d, e), "bias": w(e), "w_gate": w(e, d, f), "w_up": w(e, d, f), "w_down": w(e, f, d),
        "shared": {"w_gate": w(d, f), "w_up": w(d, f), "w_down": w(f, d)},
    }


def _share(p, first, count):
    return {k: v[first:first + count] if k.startswith("w_") else v for k, v in p.items()}


def test_the_eight_shares_add_up_to_the_uncut_layer(ref, cfg):
    """Eight chips hold experts 0-1, 2-3, ... 14-15 of sixteen (0-7 ...
    56-63 of the published 64).  Their routed parts, with the shared expert
    counted once, add up to what the reference gives for the whole layer."""
    p = _expert_weights(5)
    x = jnp.asarray(np.random.default_rng(6).normal(size=(B, 32)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        whole, loads = ref.expert_layer(p, x, p["bias"], {**M, "experts_held_first": 0, "num_experts_held": 16}, "f32")
        shared = ref.swiglu(p["shared"], x, "f32")
        parts, slots = [], 0
        for first in range(0, 16, 2):
            share = dataclasses.replace(cfg, experts_held=(first, 2))
            y, sizes, routes = stream.expert_layer(_share(p, first, 2), x, share)
            parts.append(y - shared)
            slots += int(sizes.sum())
            np.testing.assert_array_equal(sizes, np.asarray(loads)[first:first + 2])
            # Every share counts the loads of all sixteen alike.
            np.testing.assert_array_equal(routes, loads)
    assert slots == 4 * B == int(np.asarray(loads).sum())       # every slot lives on exactly one chip
    np.testing.assert_allclose(sum(parts) + shared, whole, rtol=0, atol=5e-5)


@pytest.mark.parametrize("blocks", [1, 2], ids=["grown-as-filled", "one-of-padding"])
def test_the_expert_layer_and_its_gradient_match_the_reference(ref, cfg, blocks):
    """Value and every gradient (the router's through the scores that
    weigh the chosen experts) against the reference's loop over the held
    experts, under the sigmoid router, the bias and the ungated shared
    expert."""
    cfg = dataclasses.replace(cfg, expert_blocks=blocks)
    p = _share(_expert_weights(11), 4, 4)
    rng = np.random.default_rng(12)
    x, w = (jnp.asarray(rng.normal(size=(B, 32)).astype(np.float32)) for _ in range(2))
    ours = lambda p, x: stream.expert_layer(p, x, cfg)[0]
    theirs = lambda p, x: ref.expert_layer(p, x, p["bias"], M, "f32")[0]
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(ours(p, x), theirs(p, x), rtol=0, atol=2e-5)
        got = jax.grad(lambda *a: (ours(*a) * w).sum(), argnums=(0, 1))(p, x)
        want = jax.grad(lambda *a: (theirs(*a) * w).sum(), argnums=(0, 1))(p, x)
    assert float(jnp.abs(want[0]["router"]).max()) > 0
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * max(float(np.abs(b).max()), 1.0))


# -- through the trainer's normal path ---------------------------------------------------------


def _trainer(cfg, super_steps=2, tmp=None):
    """(the trainer, the host features and probe edges it was given)."""
    rng = np.random.default_rng(0)
    topo = (rng.integers(0, N, 200).astype(np.int32), rng.integers(0, N, 200).astype(np.int32),
            rng.random(200).astype(np.float32))
    feats = rng.normal(size=(N, 2)).astype(np.float32)
    return OnlineGraphTrainer(
        OnlineGraphConfig(num_nodes=N, max_neighbors=4, batch_size=B, super_steps=super_steps, model=cfg,
                          queue_capacity=8, train=TrainConfig(seed=2)),
        node_feats=feats, topo_src=topo[0], topo_dst=topo[1], topo_rtt=topo[2], checkpoint_dir=tmp,
    ), (feats, topo)


def _biases(tr):
    got = tr.state.model_state[stream.SELECTION_BIAS]
    return np.stack([np.asarray(got[f"layer_{i}"]) for i in EXPERT_LAYERS])


def test_first_dispatch_of_the_trainer_is_the_references_leaf_by_leaf(ref, cfg):
    """Two steps of one dispatch, the selection bias moved between them:
    Adam's two moments and the weights' change by leaf, and the loss, as
    ``benchmark/check.py`` compares them on the chip, here float32 against
    float32; and the bias after the dispatch, the reference's exactly."""
    tr, (feats, topo) = _trainer(cfg)
    host = lambda tree: jax.tree_util.tree_map(np.asarray, tree)
    params0 = host(tr.state.params)
    parts = [_records(s) for s in range(2)]
    block = tuple(np.concatenate(p) for p in zip(*parts))
    with jax.default_matmul_precision("highest"):
        tr.feed_downloads(*block)
        tr.end_of_stream()
        assert tr.run(idle_timeout=5.0) == 1
        got = check.program_readings(
            [float(tr.last_loss)], params0, host(tr.state.params), host(tr.state.opt_state)
        )
        want = ref.first_steps(M, TRAIN, {
            "node_feats": feats, "topo": topo, "max_neighbors": 4,
            "batches": tuple(a.reshape(2, B) for a in block), "init_key": jax.random.PRNGKey(2),
        })
    tr.close()
    limits = {"loss_gap": 1e-6, "moment_gap": 1e-5, "moment_diff_median": 1e-5, "second_moment_gap": 1e-4, "change_gap": 1e-3}
    rows = check.compare(got, want, limits)
    assert check.verdict(rows), rows
    assert max(want["change_norm"].values()) > 0
    np.testing.assert_array_equal(_biases(tr), want["biases"][-1])
    assert np.abs(want["biases"][-1]).max() > 0


def test_the_bias_moves_by_the_rate_against_each_steps_loads(cfg):
    """One step a dispatch, two dispatches: after each, every expert's bias
    is the one before plus 0.001 where the step sent it fewer slots than
    the mean (4 x 128 / 16 = 32), less 0.001 where more, and as it was
    where as many; the loads are the step's own, over all sixteen experts."""
    tr, _ = _trainer(cfg, super_steps=1)
    before, routed = _biases(tr), np.zeros((len(EXPERT_LAYERS), 16), np.int64)
    assert not before.any()
    moved = set()
    for s in range(2):
        tr.feed_downloads(*_records(40 + s))
        assert tr.run(max_dispatches=1, idle_timeout=5.0) == 1
        total = np.asarray(tr.state.aux["expert_routes"][-1]).astype(np.int64)
        loads, routed = total - routed, total
        assert (loads.sum(1) == 4 * B).all()
        after = _biases(tr)
        step = np.float32(0.001) * np.sign(np.float32(4 * B / 16) - loads.astype(np.float32))
        np.testing.assert_array_equal(after, (before + step).astype(np.float32))
        moved |= set(np.unique(np.sign(after - before)))
        before = after
    tr.close()
    assert {-1.0, 1.0} <= moved


def test_the_bias_has_no_adam_moment_no_weight_decay_and_no_gradient(cfg):
    tr, _ = _trainer(cfg)
    names = lambda tree: {"/".join(str(getattr(k, "key", k)) for k in path)
                          for path, _ in jax.tree_util.tree_leaves_with_path(tree)}
    held = names(tr.state.params) | names(tr.state.opt_state)
    assert not [n for n in held if "selection_bias" in n or n.endswith("moe/bias")]
    adam = check.adam_moments(tr.state.opt_state)
    assert names(adam.mu) == names(tr.state.params)
    assert set(tr.state.model_state) == {stream.SELECTION_BIAS}
    assert sorted(tr.state.model_state[stream.SELECTION_BIAS]) == [f"layer_{i}" for i in EXPERT_LAYERS]
    tr.close()


def test_a_checkpoint_carries_the_bias(cfg, tmp_path):
    tr, _ = _trainer(cfg, tmp=str(tmp_path))
    tr.feed_downloads(*(np.concatenate(p) for p in zip(_records(1), _records(2))))
    assert tr.run(max_dispatches=1, idle_timeout=5.0) == 1
    tr.checkpoint()
    saved = _biases(tr)
    tr.close()
    assert saved.any()
    fresh, _ = _trainer(cfg, tmp=str(tmp_path))
    assert not _biases(fresh).any()
    assert fresh.resume()
    np.testing.assert_array_equal(_biases(fresh), saved)
    fresh.close()


def test_ranker_is_built_with_a_dense_layer_and_four_expert_layers(cfg):
    assert [(k.kind, k.window, k.rope) for k in stream.layer_kinds(cfg)] == [("attention", 0, True)] * 5
    assert list(stream.expert_layers(cfg)) == [1, 2, 3, 4]
    ranker = build_ranker(cfg)
    assert ranker.batch_multiple == L and not ranker.servable
    assert ranker.run_attrs() == {"moe_row_mover": slot_rows.XLA, "moe_grouped_carrier": grouped_matmul.XLA}
    shapes = stream.parameter_shapes(cfg, HOP_DIM, N)
    assert "layer_0.mlp.w_gate" in shapes and "layer_0.moe.router" not in shapes
    assert "layer_1.moe.shared_gate" not in shapes and "layer_1.attn.w_q" not in shapes
    assert shapes["layer_1.attn.w_kva"][1] == (32, 8 + 4)


def test_run_counts_the_loads_of_all_experts_and_the_keys_as_full(cfg, ring):
    """The dispatch's span carries the slots of the busiest expert of all
    sixteen in the busiest layer and their mean (4 x 2 x 128 / 16 = 64),
    the gauge their ratio, and the slot counters four expert layers (not
    five); the latent layers' keys are counted as kind ``full``."""
    routed0 = trainer_metrics.MOE_SLOTS_ROUTED.value()
    tr, _ = _trainer(cfg)
    for i in range(2):
        tr.feed_downloads(*(np.concatenate(p) for p in zip(_records(10 * i), _records(10 * i + 1))))
    tr.end_of_stream()
    assert tr.run(idle_timeout=5.0) == 2
    tr.close()
    assert trainer_metrics.MOE_SLOTS_ROUTED.value() - routed0 == 2 * 2 * B * 4 * 4
    spans = ring.find("trainer/dispatch")
    assert len(spans) == 2
    for span in spans:
        a = span.attributes
        assert a["moe_route_mean"] == 2 * B * 4 / 16 and a["moe_route_max"] > a["moe_route_mean"]
        assert a["attn_keys_in_band_full"] == 5 * 2 * ROWS * L * (L + 1) // 2
        assert "attn_keys_in_band_window" not in a
    ratio = spans[-1].attributes["moe_route_max"] / spans[-1].attributes["moe_route_mean"]
    assert trainer_metrics.MOE_ROUTE_MAX_OVER_MEAN.value() == pytest.approx(ratio)


def test_route_reader_reads_the_spans_and_nothing_where_none_is_counted(cfg, ring):
    from types import SimpleNamespace

    from benchmark.reduce import program_spans

    read = bench.load_module("metrics", "moe_route_max_over_mean").read
    assert read(SimpleNamespace(trace=None)) is None
    tr, _ = _trainer(cfg)
    tr.feed_downloads(*(np.concatenate(p) for p in zip(_records(1), _records(2))))
    tr.end_of_stream()
    assert tr.run(idle_timeout=5.0) == 1
    tr.close()
    (root,) = ring.find("trainer/run")
    run = SimpleNamespace(trace=SimpleNamespace(spans=[(0.0, program_spans.seconds(root), "bench/run")]))
    (span,) = ring.find("trainer/dispatch")
    ratio = span.attributes["moe_route_max"] / span.attributes["moe_route_mean"]
    assert read(run) == pytest.approx(ratio) and ratio > 1
    for name in ("moe_route_max", "moe_route_mean"):
        del span.attributes[name]
    assert read(run) is None


def test_step_scopes_name_the_latent_products_and_the_dense_layer(cfg):
    """The scopes the benchmark's readers join: ``attn/latent`` beside
    ``attn/proj`` and ``attn/core``, the dense layer's ``stream/mlp``
    (named, so ``step_unscoped_share`` does not count it), no DeltaNet,
    and the bias's rule under the router."""
    from benchmark.reduce import stream_scopes
    from benchmark.tools.program_trace import instruction_scopes

    tr, _ = _trainer(cfg)
    text = tr.dispatch_program_text()
    tr.close()
    names = set(instruction_scopes(text).values())
    found = {stream_scopes.scope_of(name) for name in names}
    assert found >= {"embed", "attn/latent", "attn/proj", "attn/core", "moe/router", "moe/dispatch",
                     "moe/experts", "moe/shared", "moe/combine", "head"}
    assert "gdn/scan" not in found
    assert any("/stream/mlp/" in n for n in names)
    scoped = bench.load_module("metrics", "step_unscoped_share").scoped
    assert all(scoped(n) for n in names if "/stream/mlp/" in n or "/stream/attn/latent/" in n)
    assert any("stream/moe/router/sign" in n for n in names)


# -- the other configurations keep their path ---------------------------------------------------


NEW_KEYS = {
    "q_lora_rank": 0, "kv_lora_rank": 0, "qk_nope_head_dim": 0, "qk_rope_head_dim": 0, "v_head_dim": 0,
    "shared_expert_gate": True, "scoring_func": "softmax", "routed_scaling_factor": 1.0,
    "selection_bias_rate": 0.0, "first_k_dense_replace": 0, "intermediate_size": 0,
}


@pytest.mark.parametrize("name", ["qwen3-next-80b-a3b-t16", "smallthinker-21b-a3b-t4", "defaults"])
def test_the_other_configurations_keep_the_new_keys_defaults(name):
    """What the two earlier stream configurations (and the defaults) are
    told leaves every key this configuration brought at the value that
    keeps their compiled step as it was: plain projections, no dense
    layer, softmax scores, no scale, no bias, the shared expert's gate
    where it has one; and their module declares no carried state."""
    if name == "defaults":
        c = stream.StreamRankerConfig()
    else:
        m = bench.load_json(bench.HERE, "configs", f"{name}.json")["model"]
        c = bench.load_module("configs", name).model_config(m)
    assert {k: getattr(c, k) for k in NEW_KEYS} == NEW_KEYS
    assert list(stream.expert_layers(c)) == list(range(c.num_hidden_layers))
    small = dataclasses.replace(
        c, hidden_size=16, num_attention_heads=2, num_key_value_heads=1, head_dim=8, linear_num_key_heads=1,
        linear_num_value_heads=2, linear_key_head_dim=8, linear_value_head_dim=8, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=8,
        shared_expert_intermediate_size=8 if c.shared_expert_intermediate_size else 0,
        experts_held=(0, 2), positions=16, attn_block=8, chunk=8, dtype=jnp.float32,
    )
    ids = jnp.zeros((2,), jnp.int32)
    v = stream.StreamRanker(small).init(jax.random.PRNGKey(0), jnp.zeros((4, 5)), None, ids, ids)
    assert set(v) == {"params", "aux"} and "expert_routes" not in v["aux"]
