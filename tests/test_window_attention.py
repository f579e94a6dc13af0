"""``models/stream.py::segment_attention`` with a window: against a dense
masked softmax, forward and backward, at block sizes that put the window's
edge inside a block and on a block's edge; at window none against the
unrolled loop over block pairs it replaced; its band by position; its
program the same size whatever the row's length; the step's count of
keys attended and keys in the band against a count by hand; and the band's
cut by the segment: on packed rows the same bits as the band by position
alone, the count of block pairs it runs against a dense mask's, and no
loop more in the compiled step.  Values, gradients and counts on the CPU,
never a time."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench
from dragonfly2_tpu.models import stream
from tests import _smallthinker_sizes, _stream_sizes
from tests.test_stream_ranker import _trainer

R, K, G, L, D = 2, 2, 3, 64, 8
SCALE = D ** -0.5
F32 = jnp.float32


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    return f(R, K, G, L, D), f(R, K, L, D), f(R, K, L, D), f(R, K, G, L, D)


@pytest.fixture(scope="module")
def seg():
    """Row 0: five segments of uneven length, one longer than every window
    tried but the widest; row 1 one segment."""
    dst = np.zeros((R, L), np.int32)
    dst[0] = np.repeat([1, 2, 3, 4, 5], [3, 30, 1, 17, 13])
    return stream.segments(jnp.asarray(dst.reshape(-1)), L)[1]


def _dense(q, k, v, seg, window):
    s = jnp.einsum("rkgqd,rksd->rkgqs", q, k, precision="highest") * SCALE
    i = jnp.arange(q.shape[3])
    back = i[:, None] - i[None, :]
    ok = (seg[:, :, None] == seg[:, None, :]) & (back >= 0)
    if window:
        ok &= back < window
    p = jax.nn.softmax(jnp.where(ok[:, None, None], s, -jnp.inf), -1)
    return jnp.einsum("rkgqs,rksd->rkgqd", p, v, precision="highest")


# the window's edge inside a block (5, 12, 20 in blocks of 8; 12 in 16), on a
# block's edge (8, 16 in 8; 16, 32 in 16), one block (64), and no window
CASES = [(5, 8), (8, 8), (9, 8), (12, 8), (16, 8), (20, 8), (12, 16), (16, 16), (32, 16), (24, 64), (0, 8), (64, 8)]


@pytest.mark.parametrize("window,block", CASES)
def test_forward_equals_a_dense_masked_softmax(qkv, seg, window, block):
    q, k, v, _ = qkv
    with jax.default_matmul_precision("highest"):
        got = stream.segment_attention(q, k, v, seg, block, SCALE, window)
    np.testing.assert_allclose(got, _dense(q, k, v, seg, window), rtol=0, atol=2e-6)


@pytest.mark.parametrize("window,block", CASES)
def test_backward_equals_the_dense_softmaxs(qkv, seg, window, block):
    q, k, v, w = qkv
    with jax.default_matmul_precision("highest"):
        ours = jax.grad(
            lambda q, k, v: (stream.segment_attention(q, k, v, seg, block, SCALE, window) * w).sum(), (0, 1, 2)
        )(q, k, v)
        theirs = jax.grad(lambda q, k, v: (_dense(q, k, v, seg, window) * w).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(ours, theirs):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)


def test_a_window_changes_the_result_where_a_segment_is_longer(qkv, seg):
    q, k, v, _ = qkv
    whole = np.asarray(stream.segment_attention(q, k, v, seg, 8, SCALE, 0))
    cut = np.asarray(stream.segment_attention(q, k, v, seg, 8, SCALE, 12))
    same = np.isclose(whole, cut, atol=1e-6).all(axis=(1, 2, 4))          # [R, L]
    pos = np.asarray(stream.segments(jnp.asarray(np.asarray(seg).reshape(-1)), L)[2])
    np.testing.assert_array_equal(same, pos < 12)


# -- window none: what the unrolled loop over block pairs gave ----------------------------


def _unrolled_scores(q_i, k_j, seg_i, seg_j, i, j, blk):
    s = jnp.einsum("rkgqd,rksd->rkgqs", q_i, k_j, preferred_element_type=F32) * SCALE
    ok = seg_i[:, :, None] == seg_j[:, None, :]
    if i * blk < (j + 1) * blk:
        ok = ok & ((i * blk + jnp.arange(blk)[:, None]) >= (j * blk + jnp.arange(blk)[None, :]))
    return s, ok[:, None, None]


def _unrolled_fwd(q, k, v, seg, blk):
    """``_attention_fwd`` as it stood before the loops: a Python loop over
    the block pairs, a body for each in the program."""
    outs, lses = [], []
    for i in range(q.shape[3] // blk):
        q_i, seg_i = q[:, :, :, i * blk:(i + 1) * blk], seg[:, i * blk:(i + 1) * blk]
        m = jnp.full(q_i.shape[:-1], -1e30, F32)
        den, acc = jnp.zeros(q_i.shape[:-1], F32), jnp.zeros(q_i.shape, F32)
        for j in range(i, -1, -1):
            sl = slice(j * blk, (j + 1) * blk)
            s, ok = _unrolled_scores(q_i, k[:, :, sl], seg_i, seg[:, sl], i, j, blk)
            s = jnp.where(ok, s, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            pr = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
            fix = jnp.exp(m - m_new)
            den = den * fix + pr.sum(-1)
            acc = acc * fix[..., None] + jnp.einsum(
                "rkgqs,rksd->rkgqd", pr.astype(v.dtype), v[:, :, sl], preferred_element_type=F32
            )
            m = m_new
        outs.append(acc / den[..., None])
        lses.append(m + jnp.log(den))
    return jnp.concatenate(outs, 3), jnp.concatenate(lses, 3)


def _unrolled_bwd(q, k, v, seg, blk, do):
    o, lse = _unrolled_fwd(q, k, v, seg, blk)
    nb = q.shape[3] // blk
    delta = jnp.sum(do.astype(F32) * o, axis=-1)
    dq = []
    dk = [jnp.zeros(k[:, :, :blk].shape, F32) for _ in range(nb)]
    dv = [jnp.zeros(v[:, :, :blk].shape, F32) for _ in range(nb)]
    for i in range(nb):
        qs = slice(i * blk, (i + 1) * blk)
        q_i, do_i, seg_i = q[:, :, :, qs], do[:, :, :, qs], seg[:, qs]
        dq_i = jnp.zeros(q_i.shape, F32)
        for j in range(i + 1):
            ks = slice(j * blk, (j + 1) * blk)
            s, ok = _unrolled_scores(q_i, k[:, :, ks], seg_i, seg[:, ks], i, j, blk)
            pr = jnp.where(ok, jnp.exp(s - lse[:, :, :, qs, None]), 0.0)
            dv[j] = dv[j] + jnp.einsum("rkgqs,rkgqd->rksd", pr.astype(do.dtype), do_i, preferred_element_type=F32)
            dp = jnp.einsum("rkgqd,rksd->rkgqs", do_i, v[:, :, ks], preferred_element_type=F32)
            ds = (pr * (dp - delta[:, :, :, qs, None]) * SCALE).astype(q.dtype)
            dq_i = dq_i + jnp.einsum("rkgqs,rksd->rkgqd", ds, k[:, :, ks], preferred_element_type=F32)
            dk[j] = dk[j] + jnp.einsum("rkgqs,rkgqd->rksd", ds, q_i, preferred_element_type=F32)
        dq.append(dq_i)
    cat = lambda parts, like, axis: jnp.concatenate(parts, axis).astype(like.dtype)
    return o.astype(q.dtype), cat(dq, q, 3), cat(dk, k, 2), cat(dv, v, 2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("block", [16, 64])
def test_window_none_is_bit_for_bit_what_the_unrolled_loop_gave(qkv, seg, block, dtype):
    """The same products in the same order (the diagonal first forward, the
    key blocks ascending backward), so the same bits: output and all three
    gradients.  (In blocks of 8 the CPU's code for an 8 x 8 product inside
    a loop differs from the unrolled one's by one unit in the last place;
    that case is held to 2e-7 below.)"""
    q, k, v, w = (a.astype(dtype) for a in qkv)

    @jax.jit
    def ours(q, k, v):
        o, pull = jax.vjp(lambda q, k, v: stream.segment_attention(q, k, v, seg, block, SCALE, 0), q, k, v)
        return (o, *pull(w))

    theirs = jax.jit(lambda q, k, v: _unrolled_bwd(q, k, v, seg, block, w))(q, k, v)
    for a, b in zip(ours(q, k, v), theirs):
        np.testing.assert_array_equal(np.asarray(a.astype(F32)), np.asarray(b.astype(F32)))


def test_window_none_in_blocks_of_eight_is_the_unrolled_loop_to_the_last_place(qkv, seg):
    q, k, v, w = qkv
    o, pull = jax.vjp(lambda q, k, v: stream.segment_attention(q, k, v, seg, 8, SCALE, 0), q, k, v)
    for a, b in zip((o, *pull(w)), _unrolled_bwd(q, k, v, seg, 8, w)):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-7 * max(1.0, float(jnp.abs(b).max())))


# -- the band, by position alone -------------------------------------------------------------


@pytest.mark.parametrize("window,block,want", [
    (0, 8, [0] * 8), (8, 8, [0, 0, 1, 2, 3, 4, 5, 6]), (9, 8, [0, 0, 1, 2, 3, 4, 5, 6]),
    (1, 8, list(range(8))), (12, 8, [0, 0, 0, 1, 2, 3, 4, 5]), (16, 8, [0, 0, 0, 1, 2, 3, 4, 5]),
    (18, 8, [0, 0, 0, 0, 1, 2, 3, 4]), (4096, 512, [0] * 9 + list(range(1, 24))),
])
def test_band_starts_at_the_block_of_the_oldest_key_a_query_block_sees(window, block, want):
    """Query block i's first query is at i * block and sees back to
    i * block - (window - 1): at a window of 4,096 in blocks of 512 a band
    is nine blocks, and 252 block pairs a row of 16,384 (528 with no
    window)."""
    got = [int(stream._band_start(jnp.int32(i), block, window)) for i in range(len(want))]
    assert got == want
    if window == 4096:
        assert sum(i - s + 1 for i, s in enumerate(got)) == 252 and sum(range(1, 33)) == 528


def _products(l, window):
    q = jnp.zeros((1, 2, 3, l, D), F32)
    k = jnp.zeros((1, 2, l, D), F32)
    seg = jnp.ones((1, l), jnp.int32)
    fn = lambda q, k, v: jax.vjp(lambda q, k, v: stream.segment_attention(q, k, v, seg, 8, SCALE, window), q, k, v)[1](q)
    return str(jax.make_jaxpr(fn)(q, k, k)).count("dot_general")


@pytest.mark.parametrize("window", [0, 16])
def test_the_program_holds_one_body_whatever_the_rows_length(window):
    """Two products a pair forward (kept for the backward's residuals: the
    forward runs once under the vjp) and five backward: seven, at 4 blocks
    a row and at 64."""
    assert _products(32, window) == _products(512, window) == 7


# -- the step's count of keys ---------------------------------------------------------------


@pytest.mark.parametrize("window", [0, 5, 12, 64, 100])
def test_keys_attended_and_in_band_against_a_count_by_hand(seg, window):
    _, _, pos = stream.segments(jnp.asarray(np.asarray(seg).reshape(-1)), L)
    attended, in_band = (int(a) for a in stream.attention_keys(pos, window))
    i = np.arange(L)
    back = i[:, None] - i[None, :]
    near = (back >= 0) & ((back < window) if window else True)
    same = np.asarray(seg)[:, :, None] == np.asarray(seg)[:, None, :]
    assert attended == int((same & near).sum())
    assert in_band == R * int(near.sum())
    assert attended <= in_band


# -- the band cut by the segment as well --------------------------------------------------

# Segment lengths of a row of 64, in blocks of 8: several segments, some
# shorter than a block; one that crosses six blocks; one segment; every
# block's edge a segment's start and every segment inside its block.
ROWS = {
    "several": [3, 30, 1, 17, 13],
    "crossing": [5, 50, 9],
    "one": [64],
    "aligned": [8, 3, 5, 8, 2, 6, 8, 8, 8, 8],
}
BLK = 8
PACKED = [("several",), ("crossing",), ("one",), ("aligned",), ("several", "crossing", "aligned"), ("crossing", "one", "several")]


def _packed_seg(rows):
    dst = np.stack([np.repeat(np.arange(len(ROWS[name])), ROWS[name]) for name in rows]).astype(np.int32)
    return stream.segments(jnp.asarray(dst.reshape(-1)), L)[1]


def _value_and_grads(q, k, v, w, seg, window):
    with jax.default_matmul_precision("highest"):
        o, pull = jax.vjp(lambda q, k, v: stream.segment_attention(q, k, v, seg, BLK, SCALE, window), q, k, v)
        return (o, *pull(w))


@pytest.mark.parametrize("window", [0, 20], ids=["full", "window20"])
@pytest.mark.parametrize("rows", PACKED, ids="+".join)
def test_the_segments_cut_is_the_band_by_position_bit_for_bit(rows, window, monkeypatch):
    """Output and all three gradients: the dense masked softmax's, and with
    tolerance 0 what the same call gives with the segment's block forced to
    0 (the band by position alone): a pair left out added exact zeros."""
    rng = np.random.default_rng(len(rows) + window)
    f = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    r = len(rows)
    q, k, v, w = f(r, K, G, L, D), f(r, K, L, D), f(r, K, L, D), f(r, K, G, L, D)
    seg = _packed_seg(rows)
    cut = _value_and_grads(q, k, v, w, seg, window)
    with jax.default_matmul_precision("highest"):
        dense = jax.vjp(lambda q, k, v: _dense(q, k, v, seg, window), q, k, v)
        dense = (dense[0], *dense[1](w))
    for a, b, atol in zip(cut, dense, (2e-6, 1e-5, 1e-5, 1e-5)):
        np.testing.assert_allclose(a, b, rtol=0, atol=atol)
    monkeypatch.setattr(stream, "_segment_block", lambda seg, blk: jnp.zeros((seg.shape[0], seg.shape[1] // blk), jnp.int32))
    for a, b in zip(cut, _value_and_grads(q, k, v, w, seg, window)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _pairs_by_a_dense_mask(seg, window):
    """For every query block, the key blocks from the first that holds a
    key some query of the block attends to the block's own, summed: each
    row by itself."""
    i = np.arange(L)
    back = i[:, None] - i[None, :]
    near = (back >= 0) & ((back < window) if window else True)
    ok = (seg[:, :, None] == seg[:, None, :]) & near                        # [R, L, L]
    held = ok.reshape(-1, L // BLK, BLK, L // BLK, BLK).any(axis=(2, 4))    # [R, query block, key block]
    return int((np.arange(L // BLK) + 1 - held.argmax(-1)).sum())


@pytest.mark.parametrize("window", [0, 20], ids=["full", "window20"])
@pytest.mark.parametrize("rows", PACKED, ids="+".join)
def test_pairs_run_and_in_band_against_a_count_over_a_dense_mask(rows, window):
    seg = _packed_seg(rows)
    run, in_band = (int(a) for a in stream.attention_pairs(seg, BLK, window))
    assert run == _pairs_by_a_dense_mask(np.asarray(seg), window)
    assert in_band == len(rows) * _pairs_by_a_dense_mask(np.ones((1, L), np.int32), window)
    assert run <= in_band
    if rows == ("one",):
        assert run == in_band == (26 if window else 36)
    elif rows == ("aligned",):
        assert run == L // BLK
    else:
        assert run < in_band
    # What the loop of a call that holds all the rows runs: the least first
    # key block over them, so no fewer pairs than its slowest row's.
    first = np.asarray(stream._first_key_blocks(seg, BLK, window))
    assert int((np.arange(L // BLK) + 1 - first.min(0)).sum()) >= max(
        _pairs_by_a_dense_mask(np.asarray(seg)[r:r + 1], window) for r in range(len(rows))
    )


@pytest.mark.parametrize("sizes,loops", [(_smallthinker_sizes, 51), (_stream_sizes, 41)], ids=lambda v: getattr(v, "NAME", None))
def test_the_compiled_step_holds_no_more_loops_than_the_band_by_position_did(sizes, loops):
    """The tiny cells' dispatch, compiled here: 51 and 41 ``while`` loops
    when the band was cut by position alone (this sandbox, the parent of
    the cut by the segment).  The cut is a bound of loops that were there."""
    cfg = bench.load_module("configs", sizes.NAME).model_config(sizes.M)
    tr = _trainer(dataclasses.replace(cfg, chunk=8, attn_block=8))
    text = tr.dispatch_program_text()
    tr.close()
    assert 0 < text.count(" while(") <= loops
