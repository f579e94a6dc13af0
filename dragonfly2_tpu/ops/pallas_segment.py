"""Pallas TPU kernel: segment-sum as one-hot MXU matmuls.

Edge→node scatter-add is the op XLA lowers worst on TPU (scatter
serializes; sort+segmented-scan burns VPU cycles).  The TPU-native trick:
a block of E edges writing into a block of N nodes is exactly

    out[NB, D] += onehot[NB, EB] @ values[EB, D]

— a matmul the MXU eats.  The kernel tiles the edge stream into blocks
pre-bucketed by destination node block (host prep pads each node block's
edge run), prefetches the per-block output index + first-visit flag as
scalars, and accumulates in VMEM across sequential grid steps that revisit
the same output block.

Status: compiles for the v5e and matches the oracle at 1M edges × 128
feats → 100k segments in both precisions (``chip_smoke.py`` stage C);
its time against XLA's sort-based lowering on today's code: not
measured.  ``exact=True`` f32-HIGHEST accumulation is the default;
``exact=False`` runs native bf16 MXU passes (rel err ~2e-3) for gradient
traffic.  The grid is one sequential step per edge block, so narrow
blocks drown in grid overhead and very wide ones press on VMEM; the
default is 512-edge × 256-node blocks.

Correctness oracle: ops/aggregate.segment_sum.  CPU tests run the same
kernel in interpreter mode.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def bucket_edges_by_block(
    segment_ids: np.ndarray,
    num_segments: int,
    *,
    node_block: int = 128,
    edge_block: int = 128,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Host prep: bucket the edge stream by destination node block.

    Returns (perm, dst_local, weight, block_node, is_first):
    - perm      [E_pad] — edge index into the original stream (0 for pads)
    - dst_local [E_pad] — destination offset within its node block
    - weight    [E_pad] — 1.0 real edge / 0.0 padding
    - block_node[n_edge_blocks] — node-block index each edge block writes
    - is_first  [n_edge_blocks] — 1 on the first edge block of a node block
    """
    segment_ids = np.asarray(segment_ids)
    order = np.argsort(segment_ids, kind="stable")
    n_node_blocks = (num_segments + node_block - 1) // node_block
    sorted_ids = segment_ids[order]
    # Edge run boundaries per node block.
    bounds = np.searchsorted(
        sorted_ids, np.arange(n_node_blocks + 1) * node_block
    )
    perm_parts, dstl_parts, w_parts = [], [], []
    block_node, is_first = [], []
    for j in range(n_node_blocks):
        lo, hi = bounds[j], bounds[j + 1]
        run = order[lo:hi]
        n = len(run)
        # A node block with no edges still needs one all-padding block so
        # its (is_first) visit zero-initializes the output tile.
        n_pad = max(((n + edge_block - 1) // edge_block) * edge_block, edge_block)
        pad = n_pad - n
        perm_parts.append(np.concatenate([run, np.zeros(pad, dtype=run.dtype)]))
        dstl = segment_ids[run] - j * node_block
        dstl_parts.append(
            np.concatenate([dstl, np.zeros(pad, dtype=dstl.dtype)])
        )
        w_parts.append(
            np.concatenate([np.ones(n, np.float32), np.zeros(pad, np.float32)])
        )
        n_blocks_j = n_pad // edge_block
        block_node.extend([j] * n_blocks_j)
        is_first.extend([1] + [0] * (n_blocks_j - 1))
    return (
        np.concatenate(perm_parts).astype(np.int32),
        np.concatenate(dstl_parts).astype(np.int32),
        np.concatenate(w_parts),
        np.asarray(block_node, np.int32),
        np.asarray(is_first, np.int32),
    )


def _segment_kernel(
    block_node_ref,  # scalar prefetch [n_edge_blocks]
    is_first_ref,    # scalar prefetch [n_edge_blocks]
    vals_ref,        # [EB, D]
    dstl_ref,        # [EB, 1] int32
    w_ref,           # [EB, 1] f32
    out_ref,         # [NB, D] f32 — revisited across blocks of one node block
    *,
    node_block: int,
    edge_block: int,
    exact: bool,
):
    i = pl.program_id(0)

    @pl.when(is_first_ref[i] == 1)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    dstl = dstl_ref[:].reshape(1, edge_block)            # [1, EB]
    w = w_ref[:].reshape(1, edge_block)                  # [1, EB]
    rows = jax.lax.broadcasted_iota(jnp.int32, (node_block, edge_block), 0)
    onehot = jnp.where(rows == dstl, w, 0.0)             # [NB, EB]
    if exact:
        # HIGHEST keeps the f32 accumulate exact (6-pass f32 emulation on
        # the MXU — ~8× the matmul time of the native path).
        out_ref[:] += jnp.dot(
            onehot,
            vals_ref[:].astype(jnp.float32),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
    else:
        # Native MXU pass: bf16 multiplicands, f32 accumulate.  The
        # one-hot matrix is exact in bf16 (0/1 weights), so the only
        # rounding is the bf16 cast of the values — the right trade for
        # gradient traffic (the gather VJP), which is bf16 upstream
        # anyway.
        out_ref[:] += jnp.dot(
            onehot.astype(jnp.bfloat16),
            vals_ref[:].astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )


def segment_sum_pallas(
    values: jax.Array,
    segment_ids: np.ndarray,
    num_segments: int,
    *,
    node_block: int = 256,
    edge_block: int = 512,
    exact: bool = True,
    presorted: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Segment-sum [E, D] by dst id → [num_segments, D] on the MXU.

    ``segment_ids`` is host-side (numpy): bucketing runs once per graph
    snapshot and is reused across training steps (the graph changes far
    slower than the weights).  ``values`` may be traced.

    ``edge_block`` is the throughput lever: the grid is one sequential
    step per edge block, so 128-wide blocks drown in grid overhead
    (~8k steps for 1M edges); 1024-wide blocks amortize it 8×.
    ``exact=False`` switches to native bf16 MXU passes with f32
    accumulate (~4× faster, rel err ~2e-3) — the right trade for
    gradient traffic; the default keeps f32-exact sums.
    ``presorted=True`` means values are ALREADY in the BUCKETED layout —
    ``vals[perm]`` for the perm from ``bucket_edges_by_block`` with the
    SAME block sizes, interior per-block padding included (build the
    edge stream in this layout at dataset prep to skip the [E, D]
    permutation gather per step).  A merely destination-sorted stream is
    NOT this layout; the length check below rejects it.
    """
    perm, dstl, w, block_node, is_first = bucket_edges_by_block(
        segment_ids, num_segments, node_block=node_block, edge_block=edge_block
    )
    if presorted:
        if values.shape[0] != len(perm):
            raise ValueError(
                f"presorted values must be in the bucketed layout "
                f"(len {len(perm)}, interior pads included); got "
                f"{values.shape[0]} rows — apply vals[perm] from "
                f"bucket_edges_by_block with the same block sizes"
            )
        vals = values
    elif values.shape[0] == 0:
        # Zero edges: every bucketed slot is padding (weight 0), but the
        # pad perm indexes row 0, which doesn't exist — jnp.take would
        # refuse.  The kernel still runs one all-padding block per node
        # block so the is_first visit zero-inits every output tile.
        vals = jnp.zeros((len(perm),) + tuple(values.shape[1:]), values.dtype)
    else:
        vals = jnp.take(values, jnp.asarray(perm), axis=0)   # [E_pad, D]
    return _segment_sum_bucketed(
        vals, jnp.asarray(dstl), jnp.asarray(w),
        jnp.asarray(block_node), jnp.asarray(is_first), num_segments,
        node_block=node_block, edge_block=edge_block, exact=exact,
        interpret=interpret,
    )


def _segment_sum_bucketed(
    vals: jax.Array,       # [E_pad, D] already in bucketed order
    dstl: jax.Array,       # [E_pad]
    w: jax.Array,          # [E_pad]
    block_node: jax.Array, # [n_edge_blocks]
    is_first: jax.Array,   # [n_edge_blocks]
    num_segments: int,
    *,
    node_block: int,
    edge_block: int,
    exact: bool,
    interpret: bool = False,
) -> jax.Array:
    """Device half: kernel launch against prebuilt buckets (reused across
    training steps — the VJP path calls this directly)."""
    d = vals.shape[-1]
    n_node_blocks = (num_segments + node_block - 1) // node_block
    n_edge_blocks = block_node.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_edge_blocks,),
        in_specs=[
            pl.BlockSpec((edge_block, d), lambda i, bn, fi: (i, 0)),
            pl.BlockSpec((edge_block, 1), lambda i, bn, fi: (i, 0)),
            pl.BlockSpec((edge_block, 1), lambda i, bn, fi: (i, 0)),
        ],
        out_specs=pl.BlockSpec((node_block, d), lambda i, bn, fi: (bn[i], 0)),
    )
    kernel = functools.partial(
        _segment_kernel, node_block=node_block, edge_block=edge_block,
        exact=exact,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (n_node_blocks * node_block, d), jnp.float32
        ),
        interpret=interpret,
    )(block_node, is_first, vals, dstl.reshape(-1, 1), w.reshape(-1, 1))
    return out[:num_segments]


def make_neighbor_gather(
    indices: np.ndarray,
    num_nodes: int,
    *,
    node_block: int = 256,
    edge_block: int = 512,
    interpret: bool = False,
):
    """→ gather(table [N, D]) → [N, K, D] whose backward scatter-add runs
    on the MXU segment kernel instead of XLA's sort-based lowering
    (measured 19 → 7 ms at [1.6M rows → 100k nodes], BENCHMARKS.md §2).

    ``indices`` is the HOST-side neighbor table ([N, K] numpy): bucketing
    happens once per graph snapshot, and the returned callable closes
    over the device-resident bucket arrays.  Padded slots (index 0 with
    mask 0) contribute garbage gradient rows exactly like jnp.take's
    backward would — masks zero them upstream either way.
    """
    indices = np.asarray(indices)
    flat_ids = indices.reshape(-1).astype(np.int64)
    perm, dstl, w, block_node, is_first = bucket_edges_by_block(
        flat_ids, num_nodes, node_block=node_block, edge_block=edge_block
    )
    idx_dev = jnp.asarray(indices, dtype=jnp.int32)
    perm_dev = jnp.asarray(perm)
    dstl_dev = jnp.asarray(dstl)
    w_dev = jnp.asarray(w)
    bn_dev = jnp.asarray(block_node)
    first_dev = jnp.asarray(is_first)

    @jax.custom_vjp
    def gather(table: jax.Array) -> jax.Array:
        return jnp.take(table, idx_dev, axis=0)

    def fwd(table):
        # Residuals must be jax types: an empty array carries the primal
        # dtype for the cotangent cast.
        return gather(table), jnp.zeros((0,), table.dtype)

    def bwd(res, g):
        dt = res.dtype
        flat = g.reshape(-1, g.shape[-1])
        vals = jnp.take(flat, perm_dev, axis=0)
        grad = _segment_sum_bucketed(
            vals, dstl_dev, w_dev, bn_dev, first_dev, num_nodes,
            node_block=node_block, edge_block=edge_block, exact=False,
            interpret=interpret,
        )
        return (grad.astype(dt),)

    gather.defvjp(fwd, bwd)
    return gather
