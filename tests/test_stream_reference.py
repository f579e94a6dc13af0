"""The transfer-stream ranker's arithmetic (``models/stream.py``: Gated
DeltaNet x3 : gated attention x1, routed experts plus a shared one) held to
a reference, at tiny widths on the CPU with seeded weights: the whole
program against its plain float32 reference
(``benchmark/reference/qwen3-next-80b-a3b-t16.py``), the chunked scan
against the token-by-token recurrence, the triangular inverse against
``jnp.linalg.inv``, a packed row against its segments run apart.  The
expert layer's routing and the ranker through the trainer are in
``tests/test_stream_ranker.py``.  Values and gradients, never a time."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench
from dragonfly2_tpu.models import stream
from dragonfly2_tpu.trainer.train import _huber
from tests._stream_sizes import (  # noqa: F401 — fixtures
    HOP_DIM, L, M, N, NAME, _records, cfg, hop, ref,
)


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
