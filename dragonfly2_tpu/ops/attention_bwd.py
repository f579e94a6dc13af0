"""Pallas TPU kernel: blockwise segment attention's backward with the key
blocks' gradients summed on the chip.

``models/stream.py::segment_attention`` runs causal softmax attention in
blocks of ``blk`` queries and keys, a query block reading the key blocks
of its band: from ``first[i]`` (the later of the band's start by position
and the block that holds its first query's segment start) to its own.
Its backward, per block pair ``(i, j)`` and key head, with the query
heads of the key head's group ``G`` stacked:

    s  = q_i k_j^T * scale                 masked: segment, causal, window
    p  = exp(s - lse_i)
    dv_j += p^T do_i
    dp = do_i v_j^T
    ds = p * (dp - delta_i) * scale        rounded to q's dtype
    dq_i += ds k_j
    dk_j += ds^T q_i

The loop in XLA (``_bwd_xla``, the oracle) carries ``dk`` and ``dv`` as
float32 arrays of the whole row and adds each pair's part into them with
a ``dynamic_update_slice``: at 20 key heads of 256 that is a 10.5 MB
slice read and written again for every pair, the ten longest operations
of the GLM cell's step (PERF.md section 5).  The kernel walks a row's
pairs query block by query block, as the loop does, and holds for each
``(row, key head)`` the row's ``dk`` and ``dv`` in float32 VMEM scratch
and the query block's ``dq`` beside them: ``s``, ``p``, ``dp``, ``ds``
never leave VMEM, ``dq`` is written once a query block and ``dk``, ``dv``
once a row, each in its operand's dtype.  The five products a pair are
the loop's, operands in ``q``'s dtype and float32 accumulation; only the
order of the sums differs.  A pair's scores are held keys down and the
group's queries across, so that ``lse``, ``delta`` and the mask's bounds
are rows and no vector is turned; ``q``, ``k``, ``v``, ``do`` and the
three gradients cross the call as ``[.., d, L]``, positions along the
lanes, the layout XLA gives them around the loop and in which the
projections that make them write them, so that XLA can take the swap of
the last two axes for a layout and not a copy (in ``[.., L, d]`` the GLM
cell's latent layers wrote and read them transposed, ~38 ms a step on
the chip: PERF.md section 6).

The mask: ``seg`` does not decrease along a row, so a key ``t`` is in
query ``p``'s segment, before it and inside its window exactly where
``lo[p] <= t <= p``, ``lo[p]`` the later of the segment's first position
and ``p - window + 1`` (``_lowest_keys``).

**The pairs run are the band's** (``plan``): a flat list of visits made
outside from each row's own ``first`` (``attention_pairs`` counts the
same), prefetched as scalars; the grid has a row's most, and the visits
past a row's count repeat its last one's blocks (no copy) and compute
nothing.

**Which carrier runs** is read from the shapes (``carrier``): the kernel
on a TPU where the operands are bfloat16, the head's width whole lane
groups, the block whole lane groups too, and the row's ``dk`` and ``dv``
with the pair's tiles fit VMEM; the loop anywhere else, and as the
oracle the kernel is held to (interpret mode,
``tests/test_attention_bwd.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .delta_scan import _without_locations

F32 = jnp.float32
_LANES = 128
KERNEL, XLA = "kernel", "xla"
# The most VMEM a call may ask for: the v5e's core has 128 MiB.
_VMEM_CAP = 100 * 2**20


def cut(a, i, blk: int, axis: int):
    """Block ``i`` of ``blk`` along ``axis``."""
    return jax.lax.dynamic_slice_in_dim(a, i * blk, blk, axis)


def block_scores(q_i, k_j, seg_i, seg_j, i, j, blk: int, scale: float, window: int):
    """Scores of one block pair and which of them count: in the query's
    own segment, causal, and inside its window.  q_i [R, K, G, blk, d],
    k_j [R, K, blk, d]; ``i``, ``j`` the blocks' numbers, traced."""
    s = jnp.einsum("rkgqd,rksd->rkgqs", q_i, k_j, preferred_element_type=F32) * scale
    at = jnp.arange(blk, dtype=jnp.int32)
    back = (i - j) * blk + at[:, None] - at[None, :]                  # query's position less key's
    near = (back >= 0) & (back < window) if window else back >= 0
    ok = (seg_i[:, :, None] == seg_j[:, None, :]) & near              # [R, bq, bk]
    return s, ok[:, None, None]


def _vmem_bytes(grp: int, d: int, blk: int, positions: int) -> int:
    """What a call holds in VMEM: the row's ``dk``, ``dv`` and the query
    block's ``dq`` in float32; each block in flight twice (``q``, ``do``,
    ``dq`` of the group, ``k``, ``v``, the row's ``dk``, ``dv`` out, in
    two bytes); ``s``, ``p``, ``dp``, ``ds`` of a pair in float32."""
    scratch = (2 * positions + grp * blk) * d * 4
    blocks = 2 * 2 * (3 * grp * blk * d + 2 * blk * d + 2 * positions * d)
    tiles = 4 * blk * grp * blk * 4
    return scratch + blocks + tiles


def carrier(dtype, d: int, grp: int, block: int, positions: int) -> str:
    """``"kernel"`` where the Pallas kernel runs the backward of attention
    over rows of ``positions`` in blocks of ``block`` for heads ``d`` wide,
    ``grp`` query heads a key head, operands of ``dtype``; ``"xla"`` where
    the loop does: the one test ``segment_attention``'s backward and the
    trainer's span both ask."""
    blk = min(block, positions)
    fits = (
        jnp.dtype(dtype) == jnp.bfloat16
        and d % _LANES == 0 and blk % _LANES == 0 and positions % blk == 0
        and _vmem_bytes(grp, d, blk, positions) <= _VMEM_CAP
    )
    return KERNEL if jax.default_backend() == "tpu" and fits else XLA


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# -- the loop ------------------------------------------------------------------------------


def _bwd_xla(q, k, v, seg, lse, delta, do, first, blk: int, scale: float, window: int):
    """The loop over query blocks, each over its band's key blocks from the
    least ``first`` over the call's rows, ``dk`` and ``dv`` carried whole in
    float32."""
    l = q.shape[3]
    first = first.min(0)                                              # [L // blk]

    def query_block(i, grads):
        dq, dk, dv = grads
        q_i, do_i, seg_i = cut(q, i, blk, 3), cut(do, i, blk, 3), cut(seg, i, blk, 1)
        lse_i, delta_i = cut(lse, i, blk, 3)[..., None], cut(delta, i, blk, 3)[..., None]

        def pair(j, carry):
            dq_i, dk, dv = carry
            k_j, v_j = cut(k, j, blk, 2), cut(v, j, blk, 2)
            s, ok = block_scores(q_i, k_j, seg_i, cut(seg, j, blk, 1), i, j, blk, scale, window)
            pr = jnp.where(ok, jnp.exp(s - lse_i), 0.0)
            dv_j = jnp.einsum("rkgqs,rkgqd->rksd", pr.astype(do.dtype), do_i, preferred_element_type=F32)
            dp = jnp.einsum("rkgqd,rksd->rkgqs", do_i, v_j, preferred_element_type=F32)
            ds = (pr * (dp - delta_i) * scale).astype(q.dtype)
            dq_i = dq_i + jnp.einsum("rkgqs,rksd->rkgqd", ds, k_j, preferred_element_type=F32)
            dk_j = jnp.einsum("rkgqs,rkgqd->rksd", ds, q_i, preferred_element_type=F32)
            add = lambda whole, part: jax.lax.dynamic_update_slice_in_dim(
                whole, cut(whole, j, blk, 2) + part, j * blk, 2
            )
            return dq_i, add(dk, dk_j), add(dv, dv_j)

        dq_i, dk, dv = jax.lax.fori_loop(
            first[i], i + 1, pair, (jnp.zeros(q_i.shape, F32), dk, dv)
        )
        return jax.lax.dynamic_update_slice_in_dim(dq, dq_i.astype(q.dtype), i * blk, 3), dk, dv

    dq, dk, dv = jax.lax.fori_loop(
        0, l // blk, query_block,
        (jnp.zeros(q.shape, q.dtype), jnp.zeros(k.shape, F32), jnp.zeros(v.shape, F32)),
    )
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# -- the kernel ------------------------------------------------------------------------------


def band_visits(positions: int, blk: int, window: int) -> int:
    """The pairs a row's band holds by position: the most a row can run,
    and the grid's length."""
    at = np.arange(positions // blk)
    start = np.maximum(at * blk - (window - 1), 0) // blk if window else np.zeros_like(at)
    return int(np.sum(at + 1 - start))


def plan(first, visits: int):
    """The kernel's visits from each row's first key blocks ``first`` [R,
    nb]: (query block [R * visits], key block [R * visits], the visits
    that compute [R]).  Query block by query block, each's key blocks
    ascending, so that a query block is one run and its diagonal its last
    visit; the visits past a row's count repeat its last."""
    nb = first.shape[1]
    first = first.astype(jnp.int32)
    count = jnp.arange(1, nb + 1, dtype=jnp.int32) - first            # [R, nb]
    reach = jnp.cumsum(count, axis=1)
    real = reach[:, -1]
    v = jnp.minimum(jnp.arange(visits, dtype=jnp.int32)[None, :], real[:, None] - 1)
    i = jnp.sum(reach[:, None, :] <= v[:, :, None], axis=-1, dtype=jnp.int32)   # [R, visits]
    took = jnp.take_along_axis(reach - count, i, axis=1)
    j = jnp.take_along_axis(first, i, axis=1) + v - took
    return i.reshape(-1), j.reshape(-1), real


def _lowest_keys(seg, window: int):
    """[R, L]: the first key position each query may read, the later of its
    segment's start and, under a window, ``p - window + 1``.  ``seg`` does
    not decrease along a row, so its start is the last position up to
    ``p`` where ``seg`` changed."""
    pos = jnp.arange(seg.shape[1], dtype=jnp.int32)
    changed = jnp.concatenate([jnp.ones_like(seg[:, :1], bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    lo = jax.lax.cummax(jnp.where(changed, pos, 0), axis=1)
    return jnp.maximum(lo, pos - (window - 1)) if window else lo


def _rows(x, grp: int):
    """[grp, n] or [1, n] -> [1, grp * n]: the group's rows side by side."""
    return jnp.concatenate([x[g:g + 1] for g in range(x.shape[0])] * (grp // x.shape[0]), axis=1)


def _side_by_side(x):
    """[G, d, n] -> [d, G * n]: the group's heads side by side."""
    return jnp.concatenate([x[g] for g in range(x.shape[0])], axis=1) if x.shape[0] > 1 else x[0]


def _kernel(
    qb_ref, kb_ref, first_ref, real_ref,
    q_ref, k_ref, v_ref, lse_ref, delta_ref, lo_ref, do_ref,
    dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, blk, scale, visits, blocks,
):
    """One visit: the block pair ``(i, j)`` of row ``r``'s key head ``h``,
    positions along the lanes: ``q``, ``do``, ``dq`` [G, d, blk]; ``k``,
    ``v`` [d, blk]; ``lse``, ``delta`` [G, blk]; ``lo`` [1, blk]; ``dk``,
    ``dv`` [d, L], the row's."""
    r, at = pl.program_id(0), pl.program_id(2)
    i, j = qb_ref[r * visits + at], kb_ref[r * visits + at]
    grp = q_ref.shape[0]

    @pl.when(at == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(at < real_ref[r])
    def _():
        @pl.when(j == first_ref[r * blocks + i])
        def _():
            dq_acc[...] = jnp.zeros_like(dq_acc)

        nt = lambda a, b: jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=F32)
        tn = lambda a, b: jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())), preferred_element_type=F32)
        nn = lambda a, b: jnp.dot(a, b, preferred_element_type=F32)
        q, do = _side_by_side(q_ref[...]), _side_by_side(do_ref[...])      # [d, G * blk]
        k, v = k_ref[...], v_ref[...]                                       # [d, blk]
        # Keys down, the group's queries across: [blk, G * blk].
        s = tn(k, q) * scale
        key = j * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, 1), 0)
        query = _rows(i * blk + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1), grp)
        ok = (key >= _rows(lo_ref[...], grp)) & (key <= query)
        pr = jnp.where(ok, jnp.exp(s - _rows(lse_ref[...], grp)), 0.0)
        dp = tn(v, do)
        ds = (pr * (dp - _rows(delta_ref[...], grp)) * scale).astype(q.dtype)
        cols = pl.ds(pl.multiple_of(j * blk, blk), blk)
        dv_acc[:, cols] += nt(do, pr.astype(do.dtype))
        dk_acc[:, cols] += nt(q, ds)
        dq_acc[...] += nn(k, ds)

        @pl.when(j == i)
        def _():
            for g in range(grp):
                dq_ref[g] = dq_acc[:, g * blk:(g + 1) * blk].astype(dq_ref.dtype)

        @pl.when(at == real_ref[r] - 1)
        def _():
            dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_kernel(q, k, v, seg, lse, delta, do, first, blk: int, scale: float, window: int):
    r, hk, grp, l, d = q.shape
    visits = band_visits(l, blk, window)
    qb, kb, real = plan(first, visits)
    lo = _lowest_keys(seg, window)[:, None, :]                        # [R, 1, L]
    # Positions along the lanes: [R, K, G, d, L] and [R, K, d, L].
    across = lambda x: jnp.swapaxes(x, -1, -2)
    of_query = pl.BlockSpec((None, None, grp, d, blk), lambda r, h, a, qb, *_: (r, h, 0, 0, qb[r * visits + a]))
    of_key = pl.BlockSpec((None, None, d, blk), lambda r, h, a, qb, kb, *_: (r, h, 0, kb[r * visits + a]))
    of_rows = pl.BlockSpec((None, None, grp, blk), lambda r, h, a, qb, *_: (r, h, 0, qb[r * visits + a]))
    of_row = pl.BlockSpec((None, None, d, l), lambda r, h, *_: (r, h, 0, 0))
    with _without_locations():
        dq, dk, dv = pl.pallas_call(
            functools.partial(_kernel, blk=blk, scale=scale, visits=visits, blocks=l // blk),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(r, hk, visits),
                in_specs=[
                    of_query, of_key, of_key, of_rows, of_rows,
                    pl.BlockSpec((None, 1, blk), lambda r, h, a, qb, *_: (r, 0, qb[r * visits + a])),
                    of_query,
                ],
                out_specs=[of_query, of_row, of_row],
                scratch_shapes=[
                    pltpu.VMEM((d, grp * blk), F32), pltpu.VMEM((d, l), F32), pltpu.VMEM((d, l), F32),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((r, hk, grp, d, l), q.dtype),
                jax.ShapeDtypeStruct((r, hk, d, l), k.dtype),
                jax.ShapeDtypeStruct((r, hk, d, l), v.dtype),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=min(_VMEM_CAP, max(32 * 2**20, _vmem_bytes(grp, d, blk, l) * 5 // 4)),
            ),
            interpret=_interpret(),
            name="attention_bwd",
        )(
            qb, kb, first.astype(jnp.int32).reshape(-1), real,
            across(q), across(k), across(v), lse, delta, lo, across(do),
        )
    return across(dq), across(dk), across(dv)


def backward(q, k, v, seg, o, lse, do, first, blk: int, scale: float, window: int, carrier: str = KERNEL):
    """(dq, dk, dv) of ``segment_attention`` in the operands' dtypes.  ``q``,
    ``do`` [R, K, G, L, d]; ``k``, ``v`` [R, K, L, d]; ``seg`` [R, L]; ``o``
    [R, K, G, L, d] and ``lse`` [R, K, G, L] float32, the forward's;
    ``first`` [R, L // blk], each row's first key block of each query
    block."""
    delta = jnp.sum(do.astype(F32) * o, axis=-1)                      # [R, K, G, L]
    run = _bwd_xla if carrier == XLA else _bwd_kernel
    return run(q, k, v, seg, lse, delta, do, first, blk, scale, window)
