"""The transfer-stream ranker (``models/stream.py``: Gated DeltaNet x3 :
gated attention x1, routed experts plus a shared one) against its plain
float32 reference (``benchmark/reference/qwen3-next-80b-a3b-t16.py``), at
tiny widths on the CPU with seeded weights; and through the online
trainer's normal path.  Counts, values and gradients, never a time."""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench
from dragonfly2_tpu.models import (
    HopConfig, StreamRankerConfig, build_ranker, require_servable, stream,
)
from dragonfly2_tpu.ops import slot_rows
from dragonfly2_tpu.trainer import metrics as trainer_metrics
from dragonfly2_tpu.trainer.online_graph import OnlineGraphConfig, OnlineGraphTrainer
from dragonfly2_tpu.trainer.train import TrainConfig, _huber
from dragonfly2_tpu.utils import tracing

NAME = "qwen3-next-80b-a3b-t16"
N, HOP_DIM, L, ROWS = 48, 10, 32, 4
B = ROWS * L

# Every width cut, every ratio kept: 2 value heads a key head, 2 queries a
# key-value head, a quarter of the head rotary, one layer in four attention,
# a quarter of the experts held.
M = dict(
    hidden_size=32, num_hidden_layers=4, full_attention_interval=4, rms_norm_eps=1e-6,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8, partial_rotary_factor=0.25,
    rope_theta=1e7, linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=8, linear_conv_kernel_dim=4, num_experts=16, num_experts_per_tok=3,
    moe_intermediate_size=16, shared_expert_intermediate_size=16, norm_topk_prob=True,
    experts_held_first=4, num_experts_held=4, positions=L, hops=2, dtype="float32",
    target_center=15.0, target_scale=1.0,
    # Two blocks of B slots whatever the routing: a quarter of 3 B slots is
    # held, so the second is all padding; a forced router overflows both.
    expert_blocks=2,
)


@pytest.fixture(scope="module")
def ref():
    return bench.load_module("reference", NAME)


@pytest.fixture(scope="module")
def cfg() -> StreamRankerConfig:
    got = bench.load_module("configs", NAME).model_config(M)
    return dataclasses.replace(got, chunk=8, attn_block=8)


def _records(seed=0, rows=ROWS):
    """Packed streams: segments of uneven length, several starting inside a
    chunk of 8, one row that is a single segment."""
    rng = np.random.default_rng(seed)
    dst = np.zeros((rows, L), np.int32)
    for r in range(rows - 1):
        cuts = np.sort(rng.choice(np.arange(1, L), size=3, replace=False))
        dst[r] = np.searchsorted(cuts, np.arange(L), side="right") + 4 * r
    dst[rows - 1] = 40
    src = rng.integers(0, N, (rows, L)).astype(np.int32)
    y = rng.normal(15.0, 1.0, (rows, L)).astype(np.float32)
    return src.reshape(-1), dst.reshape(-1), y.reshape(-1)


@pytest.fixture(scope="module")
def hop():
    return jnp.asarray(np.random.default_rng(1).normal(size=(N, HOP_DIM)).astype(np.float32))


@pytest.fixture(scope="module")
def weights(ref, cfg, hop):
    """(the flax module's parameters, the reference's own from the same
    key): equal bit for bit, each made by its own code."""
    key = jax.random.PRNGKey(3)
    ids = jnp.zeros((2,), jnp.int32)
    ours = stream.StreamRanker(cfg).init(key, hop, None, ids, ids)["params"]
    theirs = ref.init_params(key, M, HOP_DIM, N)
    return ours, theirs


def _flat(tree):
    from benchmark import check

    return check.flatten(jax.tree_util.tree_map(np.asarray, dict(tree)))


def test_reference_draws_the_programs_weights_bit_for_bit(weights):
    ours, theirs = _flat(weights[0]), weights[1]
    assert set(ours) == set(theirs)
    for k in ours:
        np.testing.assert_array_equal(ours[k], np.asarray(theirs[k]), err_msg=k)


def _program_loss_and_grads(cfg, params, hop, src, dst, y):
    model = stream.StreamRanker(cfg)
    qef = stream.previous_target(jnp.asarray(dst), jnp.asarray(y), cfg.positions)

    def loss(p):
        pred = model.apply({"params": p}, hop, None, jnp.asarray(src), jnp.asarray(dst), qef, train=True)
        return _huber(pred, jnp.asarray(y))

    got, grads = jax.jit(jax.value_and_grad(loss))(params)
    return float(got), _flat(grads)


def _reference_loss_and_grads(ref, params, hop, src, dst, y, variant="f32"):
    shape = (-1, L)
    s, d, t = (a.reshape(shape) for a in (src, dst, y))
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
def both(ref, cfg, hop, weights):
    src, dst, y = _records()
    with jax.default_matmul_precision("highest"):
        ours = _program_loss_and_grads(cfg, weights[0], hop, src, dst, y)
    return ours, _reference_loss_and_grads(ref, weights[1], hop, src, dst, y)


def _leaf_gaps(ours, theirs):
    """|g - g_ref| over the larger of |g_ref| and the median leaf's norm:
    the benchmark's own yardstick (``check.compare``'s ``moment_diff``)."""
    floor = float(np.median([np.linalg.norm(v) for v in theirs.values()]))
    return {
        k: float(np.linalg.norm(ours[k].astype(np.float64) - theirs[k]))
        / max(float(np.linalg.norm(theirs[k])), floor)
        for k in theirs
    }


# float32 against float32: two orders of summation of the same products.
# The loss is one sum of 128 terms; a leaf's gradient passes four layers, a
# chunked against a token-by-token recurrence and an online against a whole
# softmax, and reads 2e-6 at worst here.
LOSS_TOL, LEAF_TOL = 1e-6, 2e-5
LEAVES = ["embed/embedding"] + [name for name, _, _ in bench.load_module("reference", NAME).parameter_list(M, HOP_DIM, N)]


def test_loss_matches_the_reference(both):
    (ours, _), (theirs, _) = both
    assert abs(ours - theirs) <= LOSS_TOL * abs(theirs), (ours, theirs)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(both, leaf):
    (_, ours), (_, theirs) = both
    assert set(ours) == set(theirs) == set(LEAVES)
    assert np.linalg.norm(theirs[leaf]) > 0, "a leaf the loss does not reach is not compared"
    assert _leaf_gaps(ours, theirs)[leaf] <= LEAF_TOL


def test_a_reference_in_bfloat16_fails_the_same_tolerances(ref, hop, weights, both):
    """The tolerances are tight enough that the precision below float32
    does not pass for float32."""
    src, dst, y = _records()
    loss, grads = _reference_loss_and_grads(ref, weights[1], hop, src, dst, y, ref.CONTROL_BF16)
    (_, _), (want, theirs) = both
    gaps = _leaf_gaps(grads, theirs)
    failed = [k for k, v in gaps.items() if v > LEAF_TOL]
    assert abs(loss - want) > LOSS_TOL * abs(want) or failed
    assert len(failed) > len(LEAVES) // 2, sorted(gaps.items(), key=lambda kv: kv[1])[:5]


# -- the delta rule ---------------------------------------------------------------


def _scan_inputs(seed=0, r=2, l=32, hk=2, g=2, dk=8, dv=8):
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    f = lambda *s: rng.normal(size=s).astype(np.float32)
    q, k, v = unit(f(r, l, hk, dk)), unit(f(r, l, hk, dk)), f(r, l, hk, g, dv)
    decay = -0.5 * np.abs(f(r, l, hk, g))
    beta = rng.uniform(0.1, 0.9, (r, l, hk, g)).astype(np.float32)
    dst = np.zeros((r, l), np.int32)
    dst[0, 5:] = 1          # starts inside the first chunk of 8
    dst[0, 19:] = 2         # and inside the third
    dst[1, 8:] = 3          # at a chunk's first token
    dst[1, 9:] = 4          # and a segment of one record
    return q, k, v, decay, beta, dst


def _chunked(q, k, v, g, beta, dst, chunk=8):
    start, seg, _ = stream.segments(jnp.asarray(dst).reshape(-1), dst.shape[1])
    return stream.delta_rule_chunked(q, k, v, g, beta, start, seg, chunk, jnp.float32)


def _recurrent(q, k, v, g, beta, dst):
    r, l, hk, grp, dv = v.shape
    start, _, _ = stream.segments(jnp.asarray(dst).reshape(-1), l)
    wide = lambda a: jnp.repeat(a, grp, axis=2)
    o = stream.delta_rule_recurrent(
        wide(q), wide(k), v.reshape(r, l, hk * grp, dv), g.reshape(r, l, -1), beta.reshape(r, l, -1), start
    )
    return o.reshape(v.shape)


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_chunked_scan_equals_the_recurrence_forward(chunk):
    *x, dst = _scan_inputs()
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            _chunked(*x, dst, chunk=chunk), _recurrent(*x, dst), rtol=0, atol=2e-6
        )


@pytest.mark.parametrize("wrt", range(5), ids=["q", "k", "v", "g", "beta"])
def test_chunked_scan_equals_the_recurrence_in_its_gradient(wrt):
    *x, dst = _scan_inputs()
    w = np.random.default_rng(9).normal(size=x[2].shape).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        a = jax.grad(lambda *x: (_chunked(*x, dst) * w).sum(), argnums=wrt)(*x)
        b = jax.grad(lambda *x: (_recurrent(*x, dst) * w).sum(), argnums=wrt)(*x)
    np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * float(np.abs(b).max()))


@pytest.mark.parametrize("c", [16, 24, 64], ids=["one-block", "by-substitution", "merged-blocks"])
def test_unit_lower_inverse_and_its_gradient(c):
    a = np.tril(np.random.default_rng(2).normal(size=(3, c, c)).astype(np.float32) * 0.3, -1)
    with jax.default_matmul_precision("highest"):
        t = stream.unit_lower_inverse(jnp.asarray(a))
        np.testing.assert_allclose(t @ (np.eye(c) + a), np.broadcast_to(np.eye(c), a.shape), atol=1e-4)
        want = jax.grad(lambda a: jnp.linalg.inv(jnp.eye(c) + a).sum())(jnp.asarray(a))
        got = jax.grad(lambda a: stream.unit_lower_inverse(a).sum())(jnp.asarray(a))
    np.testing.assert_allclose(got, want, atol=2e-3 * float(np.abs(want).max()))


def test_unit_lower_inverse_holds_where_keys_share_a_direction():
    """Keys behind a SiLU share a direction, so A's entries are all near
    beta.  The Neumann series (I - A)(I + A^2)(I + A^4)... forms A's
    powers, whose entries reach 1e15 here, and what is left of the answer
    after they cancel in float32 is noise (NaN on the chip in this PR's
    first run); the inverse itself, a product of contractions
    I - beta k k^T, has entries no larger than one."""
    rng = np.random.default_rng(3)
    k = np.abs(rng.normal(size=(2, 64, 8))).astype(np.float32) + 2.0
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    a = np.tril(0.9 * np.einsum("bid,bjd->bij", k, k), -1).astype(np.float32)
    eye = np.eye(64, dtype=np.float32)
    series, power = eye - a, a
    for _ in range(5):
        power = power @ power
        series = series + series @ power
    assert np.abs(series @ (eye + a) - eye).max() > 1.0          # the series, in float32: lost
    with jax.default_matmul_precision("highest"):
        t = np.asarray(stream.unit_lower_inverse(jnp.asarray(a)))
    assert np.isfinite(t).all() and np.abs(t).max() <= 1.0 + 1e-5
    np.testing.assert_allclose(t @ (eye + a), np.broadcast_to(eye, a.shape), atol=1e-5)


# -- packing -----------------------------------------------------------------------


@pytest.mark.parametrize("layers", [3, 4], ids=["deltanet-only", "with-attention"])
def test_a_row_of_two_packed_segments_equals_the_two_run_apart(cfg, hop, layers):
    """[A | B] against [B | A]: each segment's predictions are the same
    wherever in a row it lies and whatever lies beside it.  The conv's
    taps, the recurrent state and attention stop at the boundary, rotary
    positions count along the row and only their differences are read,
    the previous target is nought at a segment's first record and the
    cold-start head answers there."""
    cfg = dataclasses.replace(cfg, num_hidden_layers=layers)
    rng = np.random.default_rng(4)
    a_len = 13                                   # B starts inside a chunk and an attention block
    seg = lambda n, child: (rng.integers(0, N, n).astype(np.int32), np.full(n, child, np.int32),
                            rng.normal(15, 1, n).astype(np.float32))
    a, b = seg(a_len, 7), seg(L - a_len, 9)
    ab = [np.concatenate(p) for p in zip(a, b)]
    ba = [np.concatenate(p) for p in zip(b, a)]
    ids = jnp.zeros((2,), jnp.int32)
    model = stream.StreamRanker(cfg)
    params = model.init(jax.random.PRNGKey(0), hop, None, ids, ids)["params"]

    def predict(src, dst, y):
        qef = stream.previous_target(jnp.asarray(dst), jnp.asarray(y), L)
        with jax.default_matmul_precision("highest"):
            return np.asarray(model.apply({"params": params}, hop, None, jnp.asarray(src), jnp.asarray(dst), qef))

    p_ab, p_ba = predict(*ab), predict(*ba)
    np.testing.assert_allclose(p_ab[:a_len], p_ba[L - a_len:], rtol=0, atol=2e-6)
    np.testing.assert_allclose(p_ab[a_len:], p_ba[: L - a_len], rtol=0, atol=2e-6)
    # and the model reads its history: inside a segment the answer moves with the targets before it
    # (two targets of A change places: the batch's mean and spread of previous targets stay)
    swapped = ab[2].copy()
    swapped[[1, 2]] = swapped[[2, 1]]
    moved = predict(ab[0], ab[1], swapped)
    assert np.abs(moved[3:a_len] - p_ab[3:a_len]).max() > 1e-6
    np.testing.assert_array_equal(moved[:3], p_ab[:3])
    np.testing.assert_array_equal(moved[a_len:], p_ab[a_len:])


def test_previous_target_is_shifted_inside_a_segment_and_nought_at_its_start():
    dst = jnp.asarray([3, 3, 3, 5, 5, 3, 3, 3], jnp.int32)        # two rows of four
    y = jnp.arange(1.0, 9.0)
    got = stream.previous_target(dst, y, 4)[:, 0]
    np.testing.assert_array_equal(got, [0, 1, 2, 0, 0, 0, 6, 7])


# -- the expert layer's share ------------------------------------------------------------


def _expert_weights(seed, m):
    rng = np.random.default_rng(seed)
    d, e, f = m["hidden_size"], m["num_experts"], m["moe_intermediate_size"]
    w = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32) * 0.3)
    return {
        "router": w(d, e), "w_gate": w(e, d, f), "w_up": w(e, d, f), "w_down": w(e, f, d),
        "shared": {"w_gate": w(d, f), "w_up": w(d, f), "w_down": w(f, d)}, "shared_gate": w(d, 1),
    }


def _share(p, first, count):
    cut = lambda a: a[first:first + count]
    return {**p, "w_gate": cut(p["w_gate"]), "w_up": cut(p["w_up"]), "w_down": cut(p["w_down"])}


def test_the_shares_add_up_to_the_uncut_layer(ref, cfg):
    """Four chips hold four experts each of sixteen.  Their routed parts,
    with the shared expert (which every chip computes alike) counted once,
    are what the reference gives for the whole layer with all sixteen."""
    p = _expert_weights(5, M)
    x = jnp.asarray(np.random.default_rng(6).normal(size=(B, M["hidden_size"])).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(p, x, {**M, "experts_held_first": 0, "num_experts_held": 16}, "f32")
        s = p["shared"]
        shared = jax.nn.sigmoid(x @ p["shared_gate"]) * (
            (jax.nn.silu(x @ s["w_gate"]) * (x @ s["w_up"])) @ s["w_down"]
        )
        parts, slots = [], 0
        for first in range(0, 16, 4):
            share = dataclasses.replace(cfg, experts_held=(first, 4))
            y, sizes = stream.expert_layer(_share(p, first, 4), x, share)
            parts.append(y - shared)
            slots += int(sizes.sum())
            # the reference's own share, the same share
            theirs = ref.expert_layer(_share(p, first, 4), x, {**M, "experts_held_first": first}, "f32")
            np.testing.assert_allclose(y, theirs, rtol=0, atol=2e-5)
    assert slots == M["num_experts_per_tok"] * B          # every slot lives on exactly one chip
    np.testing.assert_allclose(sum(parts) + shared, whole, rtol=0, atol=5e-5)


@pytest.fixture(params=[slot_rows.XLA, slot_rows.KERNEL])
def carrier(request, monkeypatch):
    """The expert layer's slot rows moved by each of ``ops/slot_rows.py``'s
    carriers: XLA's operations as tier-1's backend picks them, and the
    kernels (interpreted off the chip) as a TPU would."""
    if request.param == slot_rows.KERNEL:
        monkeypatch.setattr(slot_rows, "row_mover", lambda width, dtype: slot_rows.KERNEL)
    return request.param


@pytest.mark.parametrize("held,k,taken", [((0, 1), 3, 1), ((0, 4), 3, 3), ((8, 4), 3, 0), ((0, 8), 6, 6)],
                         ids=["one-expert-takes-every-token", "every-slot-held", "none-held",
                              "six-held-slots-a-token"])
def test_no_slot_is_dropped_under_a_forced_router(ref, cfg, held, k, taken, carrier):
    """Every token sends its ``k`` slots to experts 0 .. k-1.  Held or
    absent, each slot is counted once; a held expert computes every token
    (blocks of B slots: three when three slots of every token are held,
    six when six are), and the result is the reference's, whose loop over
    the held experts has no notion of capacity."""
    m = {**M, "num_experts_per_tok": k}
    p = _expert_weights(7, m)
    d = m["hidden_size"]
    x = jnp.asarray(np.abs(np.random.default_rng(8).normal(size=(B, d))).astype(np.float32) + 0.1)
    forced = np.full((d, 16), -1.0, np.float32)
    forced[:, :k] = [np.arange(k, 0, -1.0)]
    p = {**p, "router": jnp.asarray(forced)}
    first, count = held
    share = dataclasses.replace(cfg, experts_held=held, num_experts_per_tok=k)
    with jax.default_matmul_precision("highest"):
        y, sizes = jax.jit(lambda p, x: stream.expert_layer(p, x, share))(_share(p, first, count), x)
        want = ref.expert_layer(
            _share(p, first, count), x, {**m, "experts_held_first": first, "num_experts_held": count}, "f32"
        )
    slots_held = int(sizes.sum())
    top = np.argsort(-np.asarray(x @ p["router"]), axis=1)[:, :k]
    slots_absent = int(((top < first) | (top >= first + count)).sum())
    assert slots_held + slots_absent == k * B
    assert slots_held == taken * B
    assert [int(n) for n in sizes] == [B] * taken + [0] * (count - taken)
    np.testing.assert_allclose(y, want, rtol=0, atol=1e-4)


@pytest.mark.parametrize("blocks", [1, 2, 3], ids=["grown-as-filled", "one-of-padding", "two-of-padding"])
def test_routed_experts_gradient_equals_the_dense_reference(ref, cfg, blocks, carrier):
    """Whatever number of blocks the layer always runs: the rows past the
    held slots ride with the last expert at weight nought and add nothing,
    to the result or to any gradient."""
    cfg = dataclasses.replace(cfg, expert_blocks=blocks)
    p = _share(_expert_weights(11, M), 4, 4)
    x = jnp.asarray(np.random.default_rng(12).normal(size=(B, M["hidden_size"])).astype(np.float32))
    w = jnp.asarray(np.random.default_rng(13).normal(size=x.shape).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        ours = jax.grad(lambda p, x: (stream.expert_layer(p, x, cfg)[0] * w).sum(), argnums=(0, 1))(p, x)
        theirs = jax.grad(lambda p, x: (ref.expert_layer(p, x, M, "f32") * w).sum(), argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(ours), jax.tree_util.tree_leaves(theirs)):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * max(float(np.abs(b).max()), 1.0))


# -- through the trainer's normal path ----------------------------------------------------


@pytest.fixture
def ring():
    old_exporter, old_enabled = tracing.default_tracer.exporter, tracing.enabled()
    tracing.default_tracer.exporter = tracing.InMemoryExporter()
    tracing.set_enabled(True)
    try:
        yield tracing.default_tracer.exporter
    finally:
        tracing.default_tracer.exporter = old_exporter
        tracing.set_enabled(old_enabled)


def _trainer(cfg, tmp=None, **kw):
    rng = np.random.default_rng(0)
    topo = (rng.integers(0, N, 200).astype(np.int32), rng.integers(0, N, 200).astype(np.int32),
            rng.random(200).astype(np.float32))
    return OnlineGraphTrainer(
        OnlineGraphConfig(num_nodes=N, max_neighbors=4, batch_size=B, super_steps=2, model=cfg,
                          queue_capacity=8, train=TrainConfig(seed=2), **kw),
        node_feats=rng.normal(size=(N, 2)).astype(np.float32),
        topo_src=topo[0], topo_dst=topo[1], topo_rtt=topo[2], checkpoint_dir=tmp,
    )


def _feed(tr, dispatches, seed=0):
    for i in range(dispatches):
        parts = [_records(seed + 10 * i + s) for s in range(2)]
        tr.feed_downloads(*(np.concatenate(p) for p in zip(*parts)))
    tr.end_of_stream()


def test_run_counts_every_record_and_every_slot(cfg, ring):
    """``records_count_gap`` 0 through ``run()``: the same feed, dispatch
    and ledger as the hop ranker's; the expert layers' count arrives with
    the ledger, on the counters and on the dispatch's span."""
    routed0, held0 = trainer_metrics.MOE_SLOTS_ROUTED.value(), trainer_metrics.MOE_SLOTS_HELD.value()
    enq0, done0 = trainer_metrics.ONLINE_RECORDS_ENQUEUED.value(), trainer_metrics.ONLINE_RECORDS_TRAINED.value()
    tr = _trainer(cfg)
    _feed(tr, 3)
    assert tr.run(idle_timeout=5.0) == 3
    tr.close()
    assert tr.records_trained == tr.records_seen == 3 * 2 * B
    assert trainer_metrics.ONLINE_RECORDS_ENQUEUED.value() - enq0 == 3 * 2 * B
    assert trainer_metrics.ONLINE_RECORDS_TRAINED.value() - done0 == 3 * 2 * B       # the gap is 0
    routed = trainer_metrics.MOE_SLOTS_ROUTED.value() - routed0
    held = trainer_metrics.MOE_SLOTS_HELD.value() - held0
    assert routed == 3 * 2 * B * cfg.num_experts_per_tok * cfg.num_hidden_layers
    assert 0 < held < routed
    spans = ring.find("trainer/dispatch")
    assert len(spans) == 3
    per = cfg.num_hidden_layers * cfg.experts_held[1]
    assert sum(s.attributes["moe_load_mean"] * per for s in spans) == pytest.approx(held)
    assert all(s.attributes["moe_load_max"] >= s.attributes["moe_load_mean"] > 0 for s in spans)
    assert sum(s.attributes["moe_slots_held"] for s in spans) == held
    assert sum(s.attributes["moe_slots_routed"] for s in spans) == routed
    # Which carrier moved the expert layers' slot rows: tier-1 runs on the
    # CPU, where jnp.take and .at[].add do (the kernels on a TPU).
    (root,) = ring.find("trainer/run")
    assert root.attributes["moe_row_mover"] == slot_rows.XLA
    assert np.isfinite(float(tr.last_loss))
    src, dst, y = _records(99)
    assert np.isfinite(tr.eval_mae(src, dst, y))


def test_checkpoint_and_resume_continue_byte_identically(cfg, tmp_path):
    from dragonfly2_tpu.trainer.online_graph import state_hash

    def go(resume_after):
        tr = _trainer(cfg, str(tmp_path / f"ckpt{resume_after}"), checkpoint_every=1)
        _feed(tr, 2)
        if resume_after:
            assert tr.run(max_dispatches=1, idle_timeout=5.0) == 1
            tr.close()
            tr2 = _trainer(cfg, str(tmp_path / f"ckpt{resume_after}"), checkpoint_every=1)
            assert tr2.resume()
            tr2._downloads = tr._downloads
            tr = tr2
        tr.run(idle_timeout=5.0)
        tr.close()
        return state_hash(tr.state), tr.dispatch

    assert go(0) == go(1)


def test_step_scopes_name_the_new_layers(cfg):
    """Every scope of DESIGN.md 21 is on some instruction of the compiled
    dispatch, and the benchmark's reader cuts an ``op_name`` down to it."""
    from benchmark.reduce import stream_scopes
    from benchmark.tools.program_trace import instruction_scopes

    tr = _trainer(cfg)
    text = tr.dispatch_program_text()
    tr.close()
    found = {stream_scopes.scope_of(name) for name in instruction_scopes(text).values()}
    assert found >= {
        "embed", "gdn/proj", "gdn/conv", "gdn/scan", "gdn/out", "attn/proj", "attn/core",
        "moe/router", "moe/dispatch", "moe/experts", "moe/shared", "moe/combine", "head",
    }
    names = set(instruction_scopes(text).values())
    assert any("/loss/" in n or n.endswith("/loss") or "(loss)" in n for n in names)
    assert any("optimizer" in n for n in names)


def test_lowered_for_a_tpu_the_row_kernels_sit_under_the_layers_scopes(cfg, monkeypatch):
    """Where the kernels carry the slot rows, their calls are named by the
    scopes ``moe_share`` sums: the gathers ``stream/moe/dispatch``, the
    add-into ``stream/moe/combine``, forward and backward, and the grouped
    products stay ``ragged_dot`` under ``stream/moe/experts``."""
    from benchmark.reduce import stream_scopes

    monkeypatch.setattr(slot_rows, "row_mover", lambda width, dtype: slot_rows.KERNEL)
    monkeypatch.setattr(slot_rows, "_interpret", lambda: False)
    p = _share(_expert_weights(11, M), 4, 4)
    x = jnp.zeros((B, M["hidden_size"]), jnp.float32)
    loss = lambda p, x: stream.expert_layer(p, x, cfg)[0].sum()
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).trace(p, x).lower(
        lowering_platforms=("tpu",)
    ).as_text(debug_info=True)
    named = dict(re.findall(r"(#loc\d+) = loc\(\"([^\"]*)\"", text))
    calls = {}
    for line in text.splitlines():
        kernel = re.search(r'kernel_name = "(slot_rows_\w+)"', line)
        if kernel:
            where = named[re.search(r"loc\((#loc\d+)\)\s*$", line).group(1)]
            calls.setdefault(kernel.group(1), set()).add(
                (stream_scopes.scope_of(where), "transpose(" in where)
            )
    assert calls == {
        "slot_rows_gather": {("moe/dispatch", False), ("moe/dispatch", True)},
        "slot_rows_add": {("moe/combine", False), ("moe/combine", True)},
    }
    assert "ragged_dot" in text


# -- what the trainer refuses, and what stays as it was ----------------------------------------


def test_ranker_is_built_from_the_configurations_type(cfg):
    assert type(build_ranker(HopConfig()).module).__name__ == "HopRanker"
    assert build_ranker(HopConfig()).query_feats is None
    got = build_ranker(cfg)
    assert type(got.module).__name__ == "StreamRanker" and got.batch_multiple == L and not got.servable
    with pytest.raises(TypeError, match="no ranker is built from a dict"):
        build_ranker({})


def test_batch_must_be_whole_rows(cfg):
    with pytest.raises(ValueError, match="not a multiple of the 32 records"):
        OnlineGraphTrainer(
            OnlineGraphConfig(num_nodes=N, batch_size=B + 1, model=cfg),
            node_feats=np.zeros((N, 2), np.float32), topo_src=np.zeros(0, np.int32),
            topo_dst=np.zeros(0, np.int32), topo_rtt=np.zeros(0, np.float32),
        )


@pytest.mark.parametrize("where", ["export", "hop job", "gat job", "id recycling"])
def test_serving_and_jobs_refuse_the_stream_ranker_with_one_error(cfg, where):
    from dragonfly2_tpu.trainer import export, train

    with pytest.raises(NotImplementedError, match="trains through OnlineGraphTrainer.run\\(\\) only"):
        if where == "export":
            export.export_gnn_scorer(build_ranker(cfg).module, {}, np.zeros((N, 2)), None, np.arange(N))
        elif where == "hop job":
            train.train_hop_ranker(None, None, None, None, None, model_config=cfg)
        elif where == "gat job":
            train.train_gat_ranker(None, None, None, None, None, model_config=cfg)
        else:
            _trainer(cfg, node_ttl=60.0)
    require_servable(HopConfig(), "anywhere")          # and no one else


def _normal_text(text: str) -> str:
    """A compiled program's text without what names the source's lines."""
    text = re.sub(r'\s*(stack_frame_id=\d+|source_file="[^"]*"|source_line=\d+)', "", text)
    return "\n".join(l for l in text.splitlines() if not re.match(r"^\s*\d+ ", l))


def test_hop_dispatch_program_is_what_it_was_before_the_stream_ranker():
    """The hop configuration's compiled dispatch, instruction by
    instruction and scope by scope, is the text the parent of PR 27
    compiled (tiny, on the CPU backend of this container's jax 0.9.0; the
    digest was taken from a checkout of that commit with this same code).
    A PR that means to change the hop step takes a new digest."""
    nothing = np.zeros(0, np.int32)
    tr = OnlineGraphTrainer(
        OnlineGraphConfig(num_nodes=64, max_neighbors=4, batch_size=32, super_steps=2,
                          model=HopConfig(hidden=16)),
        node_feats=np.zeros((64, 12), np.float32), topo_src=nothing, topo_dst=nothing,
        topo_rtt=nothing.astype(np.float32),
    )
    text = _normal_text(tr.dispatch_program_text())
    tr.close()
    assert "hop/src" in text and "stream/" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "66f9296e38bdf093971d42ef4a2d0ea401934cd74386fa85a9d6315c6cd79ea7"
    )
