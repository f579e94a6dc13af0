"""The SmallThinker configuration of the transfer-stream ranker
(``models/stream.py``: a window with RoPE x3 : the whole segment without
positions x1, the router read before attention, ReGLU experts with the
softmax after the top-k, no shared expert) held to its plain float32
reference (``benchmark/reference/smallthinker-21b-a3b-t4.py``) at tiny
widths on the CPU with seeded weights: loss and gradients leaf by leaf,
the first dispatch's AdamW step through the trainer, the four shares of a
layer against the uncut layer, the routed experts under both row movers,
and the step's count of keys through the trainer's ledger.  Values,
gradients and counts, never a time."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench
from dragonfly2_tpu.models import build_ranker, stream
from dragonfly2_tpu.ops import attention_bwd, grouped_matmul, slot_rows
from dragonfly2_tpu.trainer import metrics as trainer_metrics
from tests._smallthinker_sizes import (  # noqa: F401 — fixtures
    B, HOP_DIM, L, M, N, NAME, ROWS, _records, cfg, hop, ref,
)
from tests.test_stream_ranker import _feed, _trainer, ring  # noqa: F401 — fixture
from tests.test_stream_reference import _flat, _leaf_gaps, _program_loss_and_grads


@pytest.fixture(scope="module")
def weights(ref, cfg, hop):
    """(the flax module's parameters, the reference's own from the same
    key): equal bit for bit, each made by its own code."""
    key = jax.random.PRNGKey(3)
    ids = jnp.zeros((2,), jnp.int32)
    ours = stream.StreamRanker(cfg).init(key, hop, None, ids, ids)["params"]
    return ours, ref.init_params(key, M, HOP_DIM, N)


def _reference_loss_and_grads(ref, params, hop, src, dst, y, variant="f32"):
    s, d, t = (a.reshape(-1, L) for a in (src, dst, y))
    prev = ref.previous_targets(d, t, M)
    table = ref.standard_table(hop)

    def loss(p):
        total = 0.0
        for r in range(s.shape[0]):
            pred = ref.row_predictions(p, table, s[r], d[r], jnp.asarray(prev[r]), M, variant)
            total = total + ref.C.huber_sum(pred, t[r])
        return total / s.size

    with jax.default_matmul_precision("highest"):
        got, grads = jax.jit(jax.value_and_grad(loss))(params)
    return float(got), {k: np.asarray(v) for k, v in grads.items()}


@pytest.fixture(scope="module")
def theirs(ref, hop, weights):
    return _reference_loss_and_grads(ref, weights[1], hop, *_records())


@pytest.fixture(scope="module")
def ours(cfg, hop, weights):
    with jax.default_matmul_precision("highest"):
        return _program_loss_and_grads(cfg, weights[0], hop, *_records())


# float32 against float32: two orders of summation of the same products (an
# online against a whole softmax, sorted slots against a loop over experts);
# a leaf reads 4e-7 at worst here.
LOSS_TOL, LEAF_TOL = 1e-6, 1e-5
LEAVES = ["embed/embedding"] + [
    name for name, _, _ in bench.load_module("reference", NAME).parameter_list(M, HOP_DIM, N)
]


def test_loss_matches_the_reference(ours, theirs):
    assert abs(ours[0] - theirs[0]) <= LOSS_TOL * abs(theirs[0]), (ours[0], theirs[0])


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(ours, theirs, leaf):
    assert set(ours[1]) == set(theirs[1]) == set(LEAVES)
    assert np.linalg.norm(theirs[1][leaf]) > 0, "a leaf the loss does not reach is not compared"
    assert _leaf_gaps(ours[1], theirs[1])[leaf] <= LEAF_TOL


def _every_layer(cfg, **change):
    return dataclasses.replace(cfg, layers=tuple(dataclasses.replace(k, **change) for k in cfg.layers))


LEFT_OUT = {
    "the window": lambda c: _every_layer(c, window=0),
    "the full layers' missing positions": lambda c: _every_layer(c, rope=True),
    "the router's early input": lambda c: dataclasses.replace(c, router_before_attention=False),
    "relu": lambda c: dataclasses.replace(c, hidden_act="silu"),
}


@pytest.mark.parametrize("what", sorted(LEFT_OUT))
def test_the_comparison_fails_with_one_part_of_the_layer_left_out(cfg, hop, weights, theirs, what):
    """The tolerances see each thing this configuration brought: a program
    without it (the same parameters, another function) is not the
    reference's by a hundred times the leaves' tolerance.  (At seeded
    weights of 0.02 a block adds little to the residual stream, so the loss
    moves in its sixth digit; the gradients are what see it.)"""
    with jax.default_matmul_precision("highest"):
        _, grads = _program_loss_and_grads(LEFT_OUT[what](cfg), weights[0], hop, *_records())
    gaps = _leaf_gaps(grads, theirs[1])
    assert max(gaps.values()) > 100 * LEAF_TOL, (what, sorted(gaps.items(), key=lambda kv: -kv[1])[:5])


def test_a_reference_in_bfloat16_fails_the_same_tolerances(ref, hop, weights, theirs):
    loss, grads = _reference_loss_and_grads(ref, weights[1], hop, *_records(), ref.CONTROL_BF16)
    failed = [k for k, v in _leaf_gaps(grads, theirs[1]).items() if v > LEAF_TOL]
    assert len(failed) > len(LEAVES) // 2


# -- one AdamW step, through the trainer's own dispatch -----------------------------------------


def test_first_dispatch_of_the_trainer_is_the_references_leaf_by_leaf(ref, cfg):
    """Two steps of one dispatch (the warm-up's first rate is 0, so the
    second step is the one that moves the weights): Adam's two moments and
    the weights' change by leaf, and the loss, as ``benchmark/check.py``
    compares them on the chip, here float32 against float32."""
    from benchmark import check

    tr = _trainer(cfg)
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
        rng = np.random.default_rng(0)      # _trainer's own topology and features
        topo = (rng.integers(0, N, 200).astype(np.int32), rng.integers(0, N, 200).astype(np.int32),
                rng.random(200).astype(np.float32))
        want = ref.first_steps(M, {"learning_rate": 3e-4, "weight_decay": 1e-4, "warmup_steps": 100}, {
            "node_feats": rng.normal(size=(N, 2)).astype(np.float32), "topo": topo, "max_neighbors": 4,
            "batches": tuple(a.reshape(2, B) for a in block), "init_key": jax.random.PRNGKey(2),
        })
    tr.close()
    limits = {"loss_gap": 1e-6, "moment_gap": 1e-5, "moment_diff_median": 1e-5, "second_moment_gap": 1e-4, "change_gap": 1e-3}
    rows = check.compare(got, want, limits)
    assert check.verdict(rows), rows
    assert max(want["change_norm"].values()) > 0


# -- the expert layer's share ------------------------------------------------------------------


def _expert_weights(seed):
    rng = np.random.default_rng(seed)
    d, e, f = M["hidden_size"], M["moe_num_primary_experts"], M["moe_ffn_hidden_size"]
    w = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32) * 0.3)
    return {"router": w(d, e), "w_gate": w(e, d, f), "w_up": w(e, d, f), "w_down": w(e, f, d)}


def _share(p, first, count):
    return {k: v if k == "router" else v[first:first + count] for k, v in p.items()}


def test_the_four_shares_add_up_to_the_uncut_layer(ref, cfg):
    """Four chips hold experts 0-3, 4-7, 8-11, 12-15 of sixteen (0-15 ...
    48-63 of the published 64).  Their parts (there is no shared expert to
    count once) add up to what the reference gives for the whole layer; the
    router reads another input than the experts do."""
    p = _expert_weights(5)
    rng = np.random.default_rng(6)
    x, r = (jnp.asarray(rng.normal(size=(B, M["hidden_size"])).astype(np.float32)) for _ in range(2))
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(p, x, r, {**M, "experts_held_first": 0, "num_experts_held": 16}, "f32")
        parts, slots = [], 0
        for first in range(0, 16, 4):
            share = dataclasses.replace(cfg, experts_held=(first, 4))
            held = _share(p, first, 4)
            y, sizes, _ = stream.expert_layer(held, x, share, stream.router_logits(held, r))
            parts.append(y)
            slots += int(sizes.sum())
            theirs = ref.expert_layer(held, x, r, {**M, "experts_held_first": first}, "f32")
            np.testing.assert_allclose(y, theirs, rtol=0, atol=2e-5)
    assert slots == M["moe_num_active_primary_experts"] * B      # every slot lives on exactly one chip
    np.testing.assert_allclose(sum(parts), whole, rtol=0, atol=5e-5)


@pytest.fixture(params=[slot_rows.XLA, slot_rows.KERNEL])
def carrier(request, monkeypatch):
    if request.param == slot_rows.KERNEL:
        monkeypatch.setattr(slot_rows, "row_mover", lambda width, dtype: slot_rows.KERNEL)
    return request.param


@pytest.mark.parametrize("blocks", [1, 2], ids=["grown-as-filled", "one-of-padding"])
def test_relu_experts_without_a_shared_one_under_both_row_movers(ref, cfg, blocks, carrier):
    """Value and every gradient (the router's through the logits it was
    handed) against the reference's loop over the held experts."""
    cfg = dataclasses.replace(cfg, expert_blocks=blocks)
    p = _share(_expert_weights(11), 4, 4)
    rng = np.random.default_rng(12)
    x, r, w = (jnp.asarray(rng.normal(size=(B, M["hidden_size"])).astype(np.float32)) for _ in range(3))
    ours = lambda p, x, r: stream.expert_layer(p, x, cfg, stream.router_logits(p, r))[0]
    theirs = lambda p, x, r: ref.expert_layer(p, x, r, M, "f32")
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(ours(p, x, r), theirs(p, x, r), rtol=0, atol=2e-5)
        got = jax.grad(lambda *a: (ours(*a) * w).sum(), argnums=(0, 1, 2))(p, x, r)
        want = jax.grad(lambda *a: (theirs(*a) * w).sum(), argnums=(0, 1, 2))(p, x, r)
    assert float(jnp.abs(want[2]).max()) > 0        # the router's input has a gradient of its own
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * max(float(np.abs(b).max()), 1.0))


def test_softmax_after_the_top_k_is_the_renormalised_softmax(cfg):
    """The two routers are one function of the logits, by two paths."""
    p = _share(_expert_weights(3), 4, 4)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(B, M["hidden_size"])).astype(np.float32))
    before = dataclasses.replace(cfg, softmax_after_topk=False, norm_topk_prob=True)
    with jax.default_matmul_precision("highest"):
        a, n_a, _ = stream.expert_layer(p, x, cfg)
        b, n_b, _ = stream.expert_layer(p, x, before)
    np.testing.assert_array_equal(n_a, n_b)
    np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)


# -- through the trainer's normal path ---------------------------------------------------------


def test_ranker_is_built_from_the_configurations_lists(cfg):
    kinds = stream.layer_kinds(cfg)
    assert [(k.kind, k.window, k.rope) for k in kinds] == [
        ("attention", 0, False), ("attention", 12, True), ("attention", 12, True), ("attention", 12, True)
    ]
    ranker = build_ranker(cfg)
    assert ranker.batch_multiple == L and not ranker.servable
    # no DeltaNet layer, no scan carrier
    assert ranker.run_attrs() == {
        "moe_row_mover": slot_rows.XLA, "moe_grouped_carrier": grouped_matmul.XLA, "attn_carrier": attention_bwd.XLA,
        "layers_attention": 4, "layers_experts": 4,
    }
    with pytest.raises(ValueError, match="layer kinds"):
        stream.layer_kinds(dataclasses.replace(cfg, num_hidden_layers=3))


def _keys_by_hand(dst, window):
    """Keys attended and keys in the band over [rows, L] records, by a
    dense mask."""
    i = np.arange(L)
    back = i[:, None] - i[None, :]
    near = (back >= 0) & ((back < window) if window else True)
    same = dst[:, :, None] == dst[:, None, :]
    return int((same & near).sum()), dst.shape[0] * int(near.sum())


def _pairs_by_hand(dst, window, blk=8):
    """Block pairs a row's loop runs (from the block that holds the first
    query's segment start, or the band's by position if later, to the
    query block's own) and block pairs in the band by position, over
    [rows, L] records."""
    run = in_band = 0
    for row in dst:
        for i in range(L // blk):
            by_position = max(i * blk - (window - 1), 0) // blk if window else 0
            by_segment = int(np.argmax(row == row[i * blk])) // blk
            run += i - max(by_position, by_segment) + 1
            in_band += i - by_position + 1
    return run, in_band


def test_run_counts_the_keys_attended_and_in_the_band_by_layer_kind(cfg, ring):
    """Three window layers and one full: the dispatch's records' keys under
    all three masks and under position alone, on the two labelled counters
    and on each dispatch's span, and beside them the block pairs the loops
    ran and those the bands hold by position (9 and 10 a row here; 252 and
    528 a row of 16,384 in blocks of 512 under a window of 4,096); which
    form of attention ran, on the run's."""
    one_row = jnp.ones((1, 16384), jnp.int32)
    assert [int(stream.attention_pairs(one_row, 512, w)[1]) for w in (4096, 0)] == [252, 528]
    c = trainer_metrics
    before = {
        (name, kind): getattr(c, name).value(kind=kind)
        for name in ("ATTN_KEYS_ATTENDED", "ATTN_KEYS_IN_BAND") for kind in stream.ATTENTION_KINDS
    }
    tr = _trainer(cfg)
    _feed(tr, 2)
    assert tr.run(idle_timeout=5.0) == 2
    tr.close()
    want = {k: 0 for k in before}
    spans = ring.find("trainer/dispatch")
    assert len(spans) == 2
    for i, span in enumerate(spans):
        dst = np.concatenate([_records(10 * i + s)[1] for s in range(2)]).reshape(-1, L)
        win, full = _keys_by_hand(dst, 12), _keys_by_hand(dst, 0)
        assert span.attributes["attn_keys_attended_window"] == 3 * win[0]
        assert span.attributes["attn_keys_in_band_window"] == 3 * win[1] == 3 * 2 * ROWS * (78 + 20 * 12)
        assert span.attributes["attn_keys_attended_full"] == full[0]
        assert span.attributes["attn_keys_in_band_full"] == full[1] == 2 * ROWS * 528
        pairs_win, pairs_full = _pairs_by_hand(dst, 12), _pairs_by_hand(dst, 0)
        assert span.attributes["attn_pairs_run_window"] == 3 * pairs_win[0] < 3 * pairs_win[1]
        assert span.attributes["attn_pairs_in_band_window"] == 3 * pairs_win[1] == 3 * 2 * ROWS * 9
        assert span.attributes["attn_pairs_run_full"] == pairs_full[0] < pairs_full[1]
        assert span.attributes["attn_pairs_in_band_full"] == pairs_full[1] == 2 * ROWS * 10
        for name, at in (("ATTN_KEYS_ATTENDED", 0), ("ATTN_KEYS_IN_BAND", 1)):
            want[name, "window"] += 3 * win[at]
            want[name, "full"] += full[at]
    for (name, kind), was in before.items():
        assert getattr(c, name).value(kind=kind) - was == want[name, kind]
    (root,) = ring.find("trainer/run")
    assert root.attributes["moe_row_mover"] == slot_rows.XLA
    assert root.attributes["moe_grouped_carrier"] == grouped_matmul.XLA
    assert "gdn_scan_carrier" not in root.attributes
    assert tr.records_trained == 2 * 2 * B


def test_pairs_run_share_reads_the_dispatches_spans_and_nothing_where_none_is_counted(cfg, ring):
    """``benchmark/metrics/attn_pairs_run_share.py`` over a window's two
    dispatches: the pairs run over the pairs in the band, all kinds
    together; on spans that carry no such count (the parent's) ``None``."""
    from types import SimpleNamespace

    from benchmark.reduce import program_spans

    read = bench.load_module("metrics", "attn_pairs_run_share").read
    assert read(SimpleNamespace(trace=None)) is None
    tr = _trainer(cfg)
    _feed(tr, 2)
    assert tr.run(idle_timeout=5.0) == 2
    tr.close()
    (root,) = ring.find("trainer/run")
    run = SimpleNamespace(trace=SimpleNamespace(spans=[(0.0, program_spans.seconds(root), "bench/run")]))
    ran = in_band = 0
    for i in range(2):
        dst = np.concatenate([_records(10 * i + s)[1] for s in range(2)]).reshape(-1, L)
        for layers, window in ((3, 12), (1, 0)):
            a, b = _pairs_by_hand(dst, window)
            ran, in_band = ran + layers * a, in_band + layers * b
    assert read(run) == pytest.approx(100.0 * ran / in_band) and 50 < read(run) < 100
    for span in ring.find("trainer/dispatch"):
        for name in [n for n in span.attributes if n.startswith("attn_pairs_")]:
            del span.attributes[name]
    assert read(run) is None


def test_step_scopes_open_the_router_in_the_blocks_first_half(cfg):
    """The scopes the benchmark's readers join, and the router's on an
    instruction of the row-by-row half (the mixer's ``while``)."""
    from benchmark.reduce import stream_scopes
    from benchmark.tools.program_trace import instruction_scopes

    tr = _trainer(cfg)
    text = tr.dispatch_program_text()
    tr.close()
    names = set(instruction_scopes(text).values())
    found = {stream_scopes.scope_of(name) for name in names}
    assert found >= {"embed", "attn/proj", "attn/core", "moe/router", "moe/dispatch", "moe/experts", "moe/combine", "head"}
    assert not {"gdn/scan", "moe/shared"} & found
    assert any("checkpoint" in n and "while" in n and "stream/moe/router" in n for n in names)
