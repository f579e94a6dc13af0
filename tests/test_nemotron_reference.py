"""The Nemotron-3-Nano configuration of the transfer-stream ranker
(``models/stream.py``: layers of one part each, ``MEMEM*EME``: a Mamba-2
mixer in its chunked form, attention without positions at sixteen query
heads a key head, 128 relu^2 experts chosen by sigmoid score plus a
selection bias beside a relu^2 shared expert) held to its plain float32
reference (``benchmark/reference/nemotron-3-nano-30b-a3b-t16.py``) at tiny
widths on the CPU with seeded weights: loss and gradients leaf by leaf,
the first dispatch's AdamW step and the selection bias's rule through the
trainer, the chunked SSD against its recurrence, the sixteen shares of a
layer against the uncut layer, the pattern's parts, and the older stream
configurations' programs, which this one leaves as they were.  Values,
gradients and programs, never a time."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, run as bench
from dragonfly2_tpu.models import build_ranker, stream
from dragonfly2_tpu.ops import attention_bwd, delta_scan, grouped_matmul, slot_rows
from dragonfly2_tpu.trainer.online_graph import OnlineGraphConfig, OnlineGraphTrainer
from dragonfly2_tpu.trainer.train import TrainConfig, _huber
from tests._nemotron_sizes import (  # noqa: F401 — fixtures
    B, HOP_DIM, L, M, N, NAME, ROWS, _records, cfg, hop, ref,
)
from tests.test_stream_reference import _flat, _leaf_gaps

TRAIN = {"learning_rate": 3e-4, "weight_decay": 1e-4, "warmup_steps": 100}
EXPERT_LAYERS = [i for i, k in enumerate(M["hybrid_override_pattern"]) if k == "E"]


@pytest.fixture(scope="module")
def weights(ref, cfg, hop):
    """(the flax module's parameters, the reference's own from the same
    key): equal bit for bit, each made by its own code."""
    key = jax.random.PRNGKey(3)
    ids = jnp.zeros((2,), jnp.int32)
    ours = stream.StreamRanker(cfg).init(key, hop, None, ids, ids)["params"]
    return ours, ref.init_params(key, M, HOP_DIM, N)


@pytest.fixture(scope="module")
def loud(weights):
    """The same weights, each Mamba-2 layer's input projection eight times
    as large: at 32 hidden dims a row of ``h W_in`` is sqrt(32 / 2688) as
    large as at the published width, the conv's output with it, and the
    SSD's part of the mixer (``dt x (B . C)`` against ``D x``) would sit
    under the tolerances; times 8 it is as large as it is at full width."""
    scale = lambda name, a: jnp.asarray(a * 8.0 if name.endswith("mamba.w_in") else a)
    ours = jax.tree_util.tree_map_with_path(lambda path, a: scale(path[-1].key, a), weights[0])
    return ours, {k: scale(k, v) for k, v in weights[1].items()}


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
        # one row's program, traced once and mapped over the rows
        row = lambda a: ref.row_predictions(p, table, *a, jnp.asarray(bias), M, variant)[0]
        pred = jax.lax.map(row, (jnp.asarray(s), jnp.asarray(d), jnp.asarray(prev)))
        return sum(ref.C.huber_sum(pred[r], t[r]) for r in range(s.shape[0])) / s.size

    with jax.default_matmul_precision("highest"):
        got, grads = jax.jit(jax.value_and_grad(loss))(params)
    return float(got), {k: np.asarray(v) for k, v in grads.items()}


@pytest.fixture(scope="module")
def theirs(ref, hop, loud, bias):
    return _reference_loss_and_grads(ref, loud[1], bias, hop, *_records())


@pytest.fixture(scope="module")
def ours(cfg, hop, loud, bias):
    return _program_loss_and_grads(cfg, loud[0], bias, hop, *_records())


# float32 against float32: two orders of summation of the same products (a
# chunked scan against the recurrence, an online against a whole softmax,
# sorted slots against a loop over experts).
LOSS_TOL, LEAF_TOL = 1e-6, 1e-5
LEAVES = ["embed/embedding"] + [
    name for name, _, _ in bench.load_module("reference", NAME).parameter_list(M, HOP_DIM, N)
]


def test_reference_draws_the_programs_weights_bit_for_bit(weights):
    ours, theirs = _flat(weights[0]), weights[1]
    assert set(ours) == set(theirs) == set(LEAVES)
    for k in ours:
        np.testing.assert_array_equal(ours[k], np.asarray(theirs[k]), err_msg=k)
    # Mamba-2's own draws: softplus(dt_bias) in [0.001, 0.1], A in [1, 16]
    dt = np.log1p(np.exp(np.asarray(theirs["layer_0.mamba.dt_bias"])))
    assert 0.001 <= dt.min() and dt.max() <= 0.1 + 1e-6
    a = np.exp(np.asarray(theirs["layer_0.mamba.A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0


def test_loss_matches_the_reference(ours, theirs):
    assert abs(ours[0] - theirs[0]) <= LOSS_TOL * abs(theirs[0]), (ours[0], theirs[0])


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(ours, theirs, leaf):
    assert set(ours[1]) == set(theirs[1]) == set(LEAVES)
    assert np.linalg.norm(theirs[1][leaf]) > 0, "a leaf the loss does not reach is not compared"
    assert _leaf_gaps(ours[1], theirs[1])[leaf] <= LEAF_TOL


def _without_the_d_skip(monkeypatch):
    mixer = stream.mamba_mixer
    monkeypatch.setattr(stream, "mamba_mixer", lambda p, *a: mixer({**p, "D": p["D"] * 0.0}, *a))


def _without_the_reset(monkeypatch):
    """The SSD's state carried across a segment's start (the conv's taps
    still stop there)."""
    ssd = stream.ssd_chunked
    monkeypatch.setattr(
        stream, "ssd_chunked",
        lambda c, b, v, g, start, seg, *a: ssd(c, b, v, g, jnp.zeros_like(start), jnp.zeros_like(seg), *a),
    )


def _layer_gaps(ref, part, cfg, loud, expert_p=None):
    """The gradients of one layer's part, the program's against the
    reference's, by ``_leaf_gaps`` (the input's among them): ``part``
    ``"M"`` the first Mamba-2 layer's mixer on the loud weights, ``"E"``
    the relu^2 expert layer on ``_expert_weights(11)``'s share (the
    program's matrices ``expert_p`` where given).  Rows of the tiny
    records' segments; a cotangent of random weights."""
    rng = np.random.default_rng(21)
    src, dst, _ = _records()
    if part == "M":
        x = jnp.asarray(rng.normal(size=(ROWS, L, 32)).astype(np.float32))
        mine, theirs = stream.nest(dict(loud[0]))["layer_0"]["mamba"], ref.nest(loud[1])["layer_0"]["mamba"]
        start, seg, pos = stream.segments(jnp.asarray(dst), L)
        ours = lambda p, x: stream.mamba_mixer(p, x, start, seg, pos, cfg)
        ref_row = lambda p, x, s, q: ref.mamba(p, x, s, q, M, "f32")
        want_fn = lambda p, x: jax.vmap(ref_row, in_axes=(None, 0, 0, 0))(p, x, start, pos)
    else:
        x = jnp.asarray(rng.normal(size=(B, 32)).astype(np.float32))
        theirs = _share(_expert_weights(11), 4, 4)
        mine = theirs if expert_p is None else expert_p
        ours = lambda p, x: stream.expert_layer(p, x, cfg)[0]
        want_fn = lambda p, x: ref.expert_layer(p, x, theirs["bias"], M, "f32")[0]
    w = jnp.asarray(rng.normal(size=x.shape).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda *a: (ours(*a) * w).sum(), argnums=(0, 1)))(mine, x)
        want = jax.jit(jax.grad(lambda *a: (want_fn(*a) * w).sum(), argnums=(0, 1)))(theirs, x)
    flat = lambda g: {**{k: np.asarray(v) for k, v in check.flatten(g[0]).items() if k != "bias"}, "x": np.asarray(g[1])}
    return _leaf_gaps(flat(got), flat(want))


LEFT_OUT = {
    "the D skip": ("M", lambda c, mp: (_without_the_d_skip(mp), c)[1], None),
    "the state's reset at a segment's start": ("M", lambda c, mp: (_without_the_reset(mp), c)[1], None),
    "the 2.5 scale": ("E", lambda c, mp: dataclasses.replace(c, routed_scaling_factor=1.0), None),
    # the layer's matrices without the bias: experts chosen by score alone
    "the selection bias": ("E", lambda c, mp: c, lambda p: {k: v for k, v in p.items() if k != "bias"}),
}


@pytest.mark.parametrize("part", ["M", "E"], ids=["mamba-2", "relu2-experts"])
def test_the_layer_and_its_gradient_match_the_reference(ref, cfg, loud, part):
    """The yardstick the next test's omissions are read by: the whole
    layer's gradients, the reference's within the leaves' tolerance."""
    held = dataclasses.replace(cfg, experts_held=(4, 4))
    gaps = _layer_gaps(ref, part, held, loud)
    assert max(gaps.values()) <= LEAF_TOL, sorted(gaps.items(), key=lambda kv: -kv[1])[:5]


@pytest.mark.parametrize("what", sorted(LEFT_OUT))
def test_the_comparison_fails_with_one_part_of_the_layer_left_out(ref, cfg, loud, what, monkeypatch):
    """The tolerances see each thing this configuration brought: a layer
    without it (the same parameters, another function) is not the
    reference's by a hundred times the leaves' tolerance.  Read a layer
    at a time, the weights and inputs at the published widths' scale."""
    part, change, params = LEFT_OUT[what]
    changed = change(dataclasses.replace(cfg, experts_held=(4, 4)), monkeypatch)
    mine = params(_share(_expert_weights(11), 4, 4)) if params else None
    gaps = _layer_gaps(ref, part, changed, loud, mine)
    assert max(gaps.values()) > 100 * LEAF_TOL, (what, sorted(gaps.items(), key=lambda kv: -kv[1])[:5])


def test_a_reference_in_bfloat16_fails_the_same_tolerances(ref, hop, loud, bias, theirs):
    _, grads = _reference_loss_and_grads(ref, loud[1], bias, hop, *_records(), ref.CONTROL_BF16)
    failed = [k for k, v in _leaf_gaps(grads, theirs[1]).items() if v > LEAF_TOL]
    assert len(failed) > len(LEAVES) // 2


# -- the chunked SSD against its recurrence -------------------------------------------------


@pytest.mark.parametrize("starts", [(8, 16), (5, 13, 27), (9, 17, 25), ()],
                         ids=["at-a-chunks-edge", "inside-a-chunk", "right-after-an-edge", "one-segment"])
def test_the_chunked_ssd_is_its_recurrence(starts):
    """``ssd_chunked`` (chunks of 8, the state carried between them by
    ``delta_scan.chunk_scan`` with no delta correction) against
    ``ssd_recurrent``, token by token, in float32: segments that start at
    a chunk's edge, inside a chunk and right after an edge; decays from
    nearly 1 to e^-4 a token; B and C a group's for its four heads."""
    rng = np.random.default_rng(len(starts) + sum(starts))
    r, hk, grp, dk, dv = 2, 2, 4, 8, 4
    first = np.zeros((r, L), bool)
    first[:, 0] = True
    first[0, list(starts)] = True
    start = jnp.asarray(first)
    seg = jnp.cumsum(start.astype(jnp.int32), axis=1)
    c, b = (jnp.asarray(rng.normal(size=(r, L, hk, dk)).astype(np.float32)) for _ in range(2))
    v = jnp.asarray(rng.normal(size=(r, L, hk, grp, dv)).astype(np.float32))
    g = -jnp.asarray(rng.uniform(0.001, 4.0, size=(r, L, hk, grp)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        got = stream.ssd_chunked(c, b, v, g, start, seg, 8, jnp.float32)
        want = stream.ssd_recurrent(c, b, v, g, start)
        gw = jax.grad(lambda *a: (stream.ssd_chunked(*a, start, seg, 8, jnp.float32) ** 2).sum(), argnums=(0, 1, 2, 3))(c, b, v, g)
        gr = jax.grad(lambda *a: (stream.ssd_recurrent(*a, start) ** 2).sum(), argnums=(0, 1, 2, 3))(c, b, v, g)
    assert got.shape == (r, L, hk, grp, dv)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * float(np.abs(want).max()))
    for a, w in zip(gw, gr):
        np.testing.assert_allclose(a, w, rtol=0, atol=2e-5 * float(np.abs(w).max()))
    # the reset is real: the second row, one segment, differs from the first
    if starts:
        assert not np.allclose(got[0, starts[0]:], stream.ssd_recurrent(c, b, v, g, jnp.zeros_like(start).at[:, 0].set(True))[0, starts[0]:])


def test_the_scan_carrier_at_64_wide_heads_is_the_lax_scan(monkeypatch):
    """The published SSD (state 128, heads 64, chunks of 128, bfloat16)
    is no whole lane group wide in its values: ``delta_scan`` leaves it
    to the ``lax.scan`` on a TPU too, as ``trainer/run`` is told."""
    m = bench.load_json(bench.HERE, "configs", f"{NAME}.json")["model"]
    published = bench.load_module("configs", NAME).model_config(m)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    attrs = stream.carrier_attrs(published)
    assert attrs["ssm_scan_carrier"] == delta_scan.XLA
    assert delta_scan.scan_carrier(jnp.bfloat16, 128, 128, 128) == delta_scan.KERNEL
    # 1856 = 14.5 x 128: every grouped product of the relu^2 experts on ragged_dot
    assert attrs["moe_grouped_carrier"] == grouped_matmul.XLA
    assert (attrs["layers_mamba"], attrs["layers_attention"], attrs["layers_experts"]) == (4, 1, 4)


# -- the pattern --------------------------------------------------------------------------------


def test_the_pattern_is_parsed_to_the_layers_parts():
    mixers, feeds = stream.hybrid_layers("MEMEM*EME")
    assert [(k.kind, k.rope) for k in mixers] == [
        ("mamba", True), ("none", True), ("mamba", True), ("none", True), ("mamba", True),
        ("attention", False), ("none", True), ("mamba", True), ("none", True),
    ]
    assert feeds == ("none", "experts", "none", "experts", "none", "none", "experts", "none", "experts")
    published = bench.load_json(bench.HERE, "configs", f"{NAME}.json")["hybrid_override_pattern"]
    mixers, feeds = stream.hybrid_layers(published)
    kinds = [k.kind for k in mixers]
    assert (kinds.count("mamba"), kinds.count("attention"), feeds.count("experts"), len(kinds)) == (23, 6, 23, 52)
    with pytest.raises(ValueError, match="no layer for"):
        stream.hybrid_layers("M-E")


def test_ranker_is_built_with_single_part_layers(cfg):
    assert list(stream.expert_layers(cfg)) == EXPERT_LAYERS == [1, 3, 6, 8]
    ranker = build_ranker(cfg)
    assert ranker.batch_multiple == L and not ranker.servable
    assert ranker.run_attrs() == {
        "moe_row_mover": slot_rows.XLA, "moe_grouped_carrier": grouped_matmul.XLA, "attn_carrier": attention_bwd.XLA,
        "ssm_scan_carrier": delta_scan.XLA, "layers_attention": 1, "layers_experts": 4, "layers_mamba": 4,
    }
    shapes = stream.parameter_shapes(cfg, HOP_DIM, N)
    assert "layer_0.norm2" not in shapes and "layer_1.norm1" not in shapes
    assert "layer_1.moe.w_gate" not in shapes and "layer_1.moe.shared.w_gate" not in shapes
    assert shapes["layer_0.mamba.w_in"][1] == (32, 2 * 64 + 2 * 2 * 8 + 16)
    with pytest.raises(ValueError, match="feed-forwards"):
        stream.feed_forwards(dataclasses.replace(cfg, feed_forwards=("none",)))


# -- the expert layer's share ------------------------------------------------------------------


def _expert_weights(seed):
    rng = np.random.default_rng(seed)
    d, e, f = 32, M["n_routed_experts"], M["moe_intermediate_size"]
    w = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32) * 0.3)
    return {
        "router": w(d, e), "bias": w(e), "w_up": w(e, d, f), "w_down": w(e, f, d),
        "shared": {"w_up": w(d, 24), "w_down": w(24, d)},
    }


def _share(p, first, count):
    return {k: v[first:first + count] if k.startswith("w_") else v for k, v in p.items()}


def test_the_sixteen_shares_add_up_to_the_uncut_layer(ref, cfg):
    """Sixteen chips hold expert 0, 1, ... 15 of sixteen (experts 0-7 ...
    120-127 of the published 128).  Their routed parts, with the shared
    expert counted once, add up to what the reference gives for the whole
    layer."""
    p = _expert_weights(5)
    x = jnp.asarray(np.random.default_rng(6).normal(size=(B, 32)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        whole, loads = ref.expert_layer(p, x, p["bias"], {**M, "experts_held_first": 0, "num_experts_held": 16}, "f32")
        shared = ref.relu2_mlp(p["shared"], x, "f32")
        parts, slots = [], 0
        for first in range(16):
            share = dataclasses.replace(cfg, experts_held=(first, 1))
            y, sizes, routes = stream.expert_layer(_share(p, first, 1), x, share)
            parts.append(y - shared)
            slots += int(sizes.sum())
            np.testing.assert_array_equal(sizes, np.asarray(loads)[first:first + 1])
            np.testing.assert_array_equal(routes, loads)
    assert slots == 6 * B == int(np.asarray(loads).sum())       # every slot lives on exactly one chip
    np.testing.assert_allclose(sum(parts) + shared, whole, rtol=0, atol=5e-5 * float(np.abs(whole).max()))


@pytest.mark.parametrize("blocks", [1, 2], ids=["grown-as-filled", "one-of-padding"])
def test_the_relu2_expert_layer_and_its_gradient_match_the_reference(ref, cfg, blocks):
    """Value and every gradient (the router's through the scores that
    weigh the chosen experts) against the reference's loop over the held
    experts, relu^2 and no gate, routed and shared."""
    cfg = dataclasses.replace(cfg, expert_blocks=blocks)
    p = _share(_expert_weights(11), 4, 4)
    rng = np.random.default_rng(12)
    x, w = (jnp.asarray(rng.normal(size=(B, 32)).astype(np.float32)) for _ in range(2))
    ours = lambda p, x: stream.expert_layer(p, x, cfg)[0]
    theirs = lambda p, x: ref.expert_layer(p, x, p["bias"], M, "f32")[0]
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(ours(p, x), theirs(p, x), rtol=0, atol=2e-5 * float(np.abs(theirs(p, x)).max()))
        got = jax.grad(lambda *a: (ours(*a) * w).sum(), argnums=(0, 1))(p, x)
        want = jax.grad(lambda *a: (theirs(*a) * w).sum(), argnums=(0, 1))(p, x)
    assert float(jnp.abs(want[0]["router"]).max()) > 0
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * max(float(np.abs(b).max()), 1.0))


# -- through the trainer's normal path ---------------------------------------------------------


def _trainer(cfg, super_steps=2):
    """(the trainer, the host features and probe edges it was given)."""
    rng = np.random.default_rng(0)
    topo = (rng.integers(0, N, 200).astype(np.int32), rng.integers(0, N, 200).astype(np.int32),
            rng.random(200).astype(np.float32))
    feats = rng.normal(size=(N, 2)).astype(np.float32)
    return OnlineGraphTrainer(
        OnlineGraphConfig(num_nodes=N, max_neighbors=4, batch_size=B, super_steps=super_steps, model=cfg,
                          queue_capacity=8, train=TrainConfig(seed=2)),
        node_feats=feats, topo_src=topo[0], topo_dst=topo[1], topo_rtt=topo[2],
    ), (feats, topo)


@pytest.fixture(scope="module")
def dispatched(ref, cfg):
    """One trainer's first dispatch of two steps under ``highest``: (its
    readings as ``benchmark/check.py`` takes them, the reference's, the
    selection bias after the dispatch, the dispatch's program text)."""
    tr, (feats, topo) = _trainer(cfg)
    host = lambda tree: jax.tree_util.tree_map(np.asarray, tree)
    params0 = host(tr.state.params)
    block = tuple(np.concatenate(p) for p in zip(_records(0), _records(1)))
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
    biases = tr.state.model_state[stream.SELECTION_BIAS]
    text = tr.dispatch_program_text()
    tr.close()
    return got, want, biases, text


def test_first_dispatch_of_the_trainer_is_the_references_leaf_by_leaf(dispatched):
    """Two steps of one dispatch, the selection bias moved between them:
    Adam's two moments and the weights' change by leaf, and the loss, as
    ``benchmark/check.py`` compares them on the chip, here float32 against
    float32; and the bias after the dispatch, the reference's exactly."""
    got, want, biases, _ = dispatched
    limits = {"loss_gap": 1e-6, "moment_gap": 1e-5, "moment_diff_median": 1e-5, "second_moment_gap": 1e-4, "change_gap": 1e-3}
    rows = check.compare(got, want, limits)
    assert check.verdict(rows), rows
    assert max(want["change_norm"].values()) > 0
    np.testing.assert_array_equal(np.stack([np.asarray(biases[f"layer_{i}"]) for i in EXPERT_LAYERS]), want["biases"][-1])
    assert np.abs(want["biases"][-1]).max() > 0


def test_step_scopes_name_the_mamba_parts(dispatched):
    """The scopes the benchmark's readers join: ``stream/mamba/proj``,
    ``conv``, ``scan`` and ``out`` beside attention's and the experts', no
    DeltaNet; every one of them named for ``step_unscoped_share``, and
    every operation of the step under some scope of the program but the
    trainer's own (``tests/test_program_scopes.py``'s guard)."""
    from benchmark.tools.program_trace import instruction_scopes
    from dragonfly2_tpu.trainer import program_scopes
    from tests.test_program_scopes import _the_trainers

    text = dispatched[3]
    ops = [o for o in program_scopes.operations(text) if o[1] not in program_scopes.INERT]
    named = bench.load_module("metrics", "step_unscoped_share").scoped
    assert len(ops) > 1000 and not [o for o in ops if not named(o[2]) and not _the_trainers(o[2])]
    names = set(instruction_scopes(text).values())
    for part in ("proj", "conv", "scan", "out"):
        assert any(f"/stream/mamba/{part}/" in n or n.endswith(f"/stream/mamba/{part}") for n in names), part
    assert not any("/stream/gdn/" in n for n in names)
    scoped = bench.load_module("metrics", "step_unscoped_share").scoped
    mamba = [n for n in names if "/stream/mamba/" in n]
    assert mamba and all(scoped(n) for n in mamba)
    assert any("/stream/moe/shared/" in n for n in names) and any("/stream/attn/core/" in n for n in names)


# -- the other configurations keep their programs -----------------------------------------------

# sha256 of each older configuration's tiny train dispatch, lowered (no
# source locations): two steps through ``_graph_train_step``, every width
# cut as below.  Read before the single-part layers, the Mamba-2 mixer and
# the ungated experts came; a change to their programs changes these.
PROGRAMS = {
    "qwen3-next-80b-a3b-t16": "7a562776cce8aacd",
    "smallthinker-21b-a3b-t4": "f50436663617fda1",
    "glm-4-7-flash-t8": "a36f63fbd0eaafb2",
}


def _tiny(name):
    m = bench.load_json(bench.HERE, "configs", f"{name}.json")["model"]
    c = bench.load_module("configs", name).model_config(m)
    latent = bool(c.kv_lora_rank)
    return dataclasses.replace(
        c, hidden_size=16, num_attention_heads=2, num_key_value_heads=2 if latent else 1, head_dim=8,
        linear_num_key_heads=1, linear_num_value_heads=2, linear_key_head_dim=8, linear_value_head_dim=8,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=8,
        shared_expert_intermediate_size=8 if c.shared_expert_intermediate_size else 0,
        intermediate_size=8 if c.intermediate_size else 0,
        q_lora_rank=4 if latent else 0, kv_lora_rank=4 if latent else 0,
        qk_nope_head_dim=4 if latent else 0, qk_rope_head_dim=4 if latent else 0, v_head_dim=8 if latent else 0,
        experts_held=(0, 2), positions=16, attn_block=8, chunk=8,
        layers=tuple(dataclasses.replace(k, window=8 if k.window else 0) for k in c.layers) if c.layers else None,
    )


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_the_older_configurations_programs_are_unchanged(name):
    from dragonfly2_tpu.models.gnn import NeighborTable
    from dragonfly2_tpu.trainer.train import TrainState, _graph_train_step, _make_optimizer

    ranker = build_ranker(_tiny(name))
    n, k, b = 24, 4, 32
    nf = jnp.zeros((n, 5), jnp.float32)
    table = NeighborTable(jnp.zeros((n, k), jnp.int32), jnp.zeros((n, k), jnp.float32), jnp.zeros((n, k, 1), jnp.float32))
    ids = jnp.zeros((2,), jnp.int32)
    v = ranker.module.init(jax.random.PRNGKey(0), nf, table, ids, ids)
    state = TrainState.create(
        apply_fn=ranker.module.apply, params=v["params"], tx=_make_optimizer(TrainConfig(), 1000),
        dropout_rng=jax.random.PRNGKey(1), aux=v.get("aux"),
        model_state={c: v[c] for c in v if c not in ("params", "aux")},
    )

    def dispatch(state, hop, table, es, ed, y):       # OnlineGraphTrainer._train_dispatch
        def body(carry, xs):
            a, bb, t = xs
            return _graph_train_step(carry, hop, table, a, bb, t, ranker.query_feats(bb, t))
        return jax.lax.scan(body, state, (es, ed, y))

    z = lambda dt: jnp.zeros((2, b), dt)
    text = jax.jit(dispatch).lower(state, nf, table, z(jnp.int32), z(jnp.int32), z(jnp.float32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PROGRAMS[name]
