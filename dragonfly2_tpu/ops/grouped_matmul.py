"""Pallas TPU kernels: the routed experts' grouped products.

``models/stream.py::_grouped`` multiplies a block of sorted slots by its
experts, group by group, in two forms:

- ``rows(a [M, K], w [G, K, N], sizes [G]) -> [M, N]``: row ``r`` of
  ``a`` by the expert whose group holds it
  (``jax.lax.ragged_dot``), the forward's three products and the
  backward's ``dh`` and ``dxb``;
- ``by_group(a [M, K], b [M, N], sizes [G]) -> [G, K, N]``: for each
  group, ``a``'s rows of it transposed times ``b``'s
  (``jax.lax.ragged_dot_general`` with the rows as the ragged, contracted
  axis), the weights' three gradients.

Both give float32.  The groups are consecutive runs of rows, ``sizes[g]``
rows for group ``g``.  A kernel walks the row tiles of ``tm`` rows group
by group: a tile that two groups share is visited once by each, the rows
of the other group masked at the store (``rows``) or in the operands
(``by_group``), so a visit is a whole tile's product and no tile is
skipped for what its rows hold.  The visits are planned outside
(``_visits``) for the most a block can need, ``M / tm + G - 1``; those
past the block's own count repeat the last one's blocks and compute
nothing.  ``K`` and ``N`` are whole in a visit: one visit is one product
of ``[tm, K] x [K, N]`` (``rows``) or ``[K, tm] x [tm, N]``
(``by_group``), and an expert's weights are fetched once for its run of
tiles.

**The arithmetic is the oracle's.**  The factors are multiplied in
bfloat16 with float32 accumulation (``preferred_element_type``).  A
float32 factor (against bfloat16 weights, ``rows``; against a bfloat16
left factor, ``by_group``) is rounded to bfloat16 in VMEM first: that
is what the chip's ``ragged_dot`` makes of it, one pass, bit for bit
(PERF.md section 6).

**Which carrier runs** is read from what the code can observe
(``grouped_carrier``): the kernel on a TPU where the widths are whole
lane groups, the weights (``rows``) or the left factor (``by_group``)
bfloat16 and the other factor bfloat16 or float32; ``ragged_dot``
anywhere else, and as the oracle the kernel is held to (interpret mode,
``tests/test_grouped_matmul.py``).  The row tile follows the form and
the group count (``tiles``): every group edge inside a tile costs a
visit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .delta_scan import _without_locations

F32 = jnp.float32
BF16 = jnp.bfloat16
_LANES = 128
KERNEL, XLA = "kernel", "xla"
ROWS, BY_GROUP = "rows", "by_group"

_ROWS_BY_GROUP = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())), lhs_ragged_dimensions=(0,), rhs_group_dimensions=()
)


def tiles(form: str, m: int, groups: int) -> int:
    """Rows a visit takes: the largest power of two that divides ``m``,
    from 256 down (``rows``) or 512 (``by_group``), for which a block's
    group edges, ``groups - 1`` extra visits at most, add no more than a
    quarter to its ``m / tm``; else the largest that divides ``m``, else
    ``m``.  A ``rows`` visit runs as fast a row at 256 rows as at 512, so
    the smaller halves what an edge costs; a ``by_group`` visit also adds
    its product into the group's ``[K, N]`` float32 block, once a visit,
    so it takes 512 rows where the edges allow (PERF.md section 6)."""
    top = 512 if form == BY_GROUP else 256
    fits = [tm for tm in (512, 256, 128, 64, 32, 16, 8) if tm <= top and m % tm == 0]
    for tm in fits:
        if (groups - 1) * tm * 4 <= m:
            return tm
    return fits[0] if fits else m


def grouped_carrier(form: str, k: int, n: int, groups: int, dtypes) -> str:
    """``"kernel"`` where this module's kernel runs the ``form`` product of
    widths ``k`` (contracted, ``rows``; the lhs's columns, ``by_group``) and
    ``n`` over ``groups`` groups for operands of ``dtypes`` (lhs, rhs),
    ``"xla"`` where ``ragged_dot`` does: the one test ``_grouped`` and the
    trainer's span both ask."""
    lhs, rhs = (jnp.dtype(d) for d in dtypes)
    if form == ROWS:
        typed = rhs == BF16 and lhs in (BF16, F32)
    else:
        typed = lhs == BF16 and rhs in (BF16, F32)
    fits = typed and k % _LANES == 0 and n % _LANES == 0
    return KERNEL if jax.default_backend() == "tpu" and fits else XLA


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _visits(sizes, m: int, tm: int, every_group: bool, to_the_end: bool):
    """The kernel's plan for ``M / tm + G - 1`` visits: (group, row tile,
    whether the visit is the first of its tile (``rows``) or group
    (``by_group``), the groups' first rows [G + 1], the visits that
    compute [1]).  Group by group, each group's tiles in order, so a tile
    (``rows``) and a group (``by_group``) are visited in one run.
    ``every_group``: an empty group is visited once (its gradient is
    nought and has to be written); ``to_the_end``: the last group visits
    every tile after it (rows past the groups then read nought)."""
    g = sizes.shape[0]
    count_tiles = m // tm
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tm, count_tiles - 1)
    count = jnp.where(sizes > 0, (ends + tm - 1) // tm - starts // tm, 0)
    if to_the_end:
        count = count.at[-1].set(jnp.where(starts[-1] < m, count_tiles - starts[-1] // tm, 0))
    if every_group:
        count = jnp.maximum(count, 1)
    reach = jnp.cumsum(count)
    real = reach[-1]
    v = jnp.minimum(jnp.arange(count_tiles + g - 1, dtype=jnp.int32), jnp.maximum(real - 1, 0))
    group = jnp.minimum(jnp.searchsorted(reach, v, side="right"), g - 1).astype(jnp.int32)
    tile = (first[group] + v - (reach[group] - count[group])).astype(jnp.int32)
    run = tile if not every_group else group
    fresh = jnp.concatenate([jnp.ones((1,), bool), run[1:] != run[:-1]]).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return group, tile, fresh, offsets, real.reshape(1).astype(jnp.int32)


def _vmem(*block_bytes: int) -> int:
    """Four times the blocks a visit holds (each in flight twice, and as
    much again for the body's values) and 8 MiB, at least 32 MiB."""
    return int(max(32 * 2**20, 4 * sum(block_bytes) + 8 * 2**20))


def _inside(base, start, end, shape, axis: int):
    at = base + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    return (at >= start) & (at < end)


def _rows_kernel(group_ref, tile_ref, fresh_ref, offsets_ref, real_ref, a_ref, w_ref, o_ref, *, tm):
    v = pl.program_id(0)
    g = group_ref[v]
    start, end = offsets_ref[g], offsets_ref[g + 1]
    base = tile_ref[v] * tm

    @pl.when(v < real_ref[0])
    def _():
        out = jnp.dot(a_ref[...].astype(BF16), w_ref[...], preferred_element_type=F32)
        whole = (start <= base) & (end >= base + tm)

        @pl.when(whole)
        def _():
            o_ref[...] = out

        # A tile another group shares: this group's rows alone, the rest
        # as the tile's earlier visits left them (nought at its first).
        @pl.when(jnp.logical_not(whole))
        def _():
            before = jnp.where(fresh_ref[v] == 1, jnp.zeros_like(out), o_ref[...])
            o_ref[...] = jnp.where(_inside(base, start, end, out.shape, 0), out, before)


def _by_group_kernel(group_ref, tile_ref, fresh_ref, offsets_ref, real_ref, a_ref, b_ref, o_ref, *, tm):
    v = pl.program_id(0)
    g = group_ref[v]
    start, end = offsets_ref[g], offsets_ref[g + 1]
    base = tile_ref[v] * tm
    real = v < real_ref[0]
    whole = (start <= base) & (end >= base + tm)

    def product(a, b):
        out = jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())), preferred_element_type=F32)

        @pl.when(fresh_ref[v] == 1)
        def _():
            o_ref[...] = out

        @pl.when(fresh_ref[v] == 0)
        def _():
            o_ref[...] += out

    @pl.when(real & (end == start))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(real & (end > start) & whole)
    def _():
        product(a_ref[...], b_ref[...].astype(BF16))

    # A tile another group shares: its rows masked in both factors.
    @pl.when(real & (end > start) & jnp.logical_not(whole))
    def _():
        keep = _inside(base, start, end, (tm, 1), 0)
        a, b = a_ref[...], b_ref[...].astype(BF16)
        product(jnp.where(keep, a, jnp.zeros_like(a)), jnp.where(keep, b, jnp.zeros_like(b)))


def _call(kernel, name, grid, in_specs, out_spec, out_shape, vmem, plan, operands):
    with _without_locations():
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(plan), grid=grid, in_specs=in_specs, out_specs=out_spec,
            ),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * len(grid), vmem_limit_bytes=vmem,
            ),
            interpret=_interpret(),
            name=name,
        )(*plan, *operands)


def rows(a, w, sizes):
    """``jax.lax.ragged_dot(a, w, sizes, preferred_element_type=float32)``
    as the chip computes it, by the kernel: ``a [M, K]`` bfloat16 (or
    float32, rounded), ``w [G, K, N]`` bfloat16, ``sizes [G]``."""
    m, k = a.shape
    g, _, n = w.shape
    tm = tiles(ROWS, m, g)
    plan = _visits(sizes, m, tm, every_group=False, to_the_end=True)
    vmem = _vmem(tm * k * a.dtype.itemsize, k * n * 2, tm * n * 4)
    return _call(
        functools.partial(_rows_kernel, tm=tm), "grouped_matmul_rows", (m // tm + g - 1,),
        [
            pl.BlockSpec((tm, k), lambda v, group, tile, *_: (tile[v], 0)),
            pl.BlockSpec((None, k, n), lambda v, group, *_: (group[v], 0, 0)),
        ],
        pl.BlockSpec((tm, n), lambda v, group, tile, *_: (tile[v], 0)),
        jax.ShapeDtypeStruct((m, n), F32), vmem, plan, (a, w),
    )


def by_group(a, b, sizes):
    """``jax.lax.ragged_dot_general`` with the rows ragged and contracted,
    float32 out, as the chip computes it, by the kernel: ``a [M, K]``
    bfloat16, ``b [M, N]`` bfloat16 (or float32, rounded), ``sizes [G]``
    -> ``[G, K, N]``; an empty group's is nought."""
    m, k = a.shape
    n = b.shape[1]
    g = sizes.shape[0]
    tm = tiles(BY_GROUP, m, g)
    plan = _visits(sizes, m, tm, every_group=True, to_the_end=False)
    vmem = _vmem(tm * k * 2, tm * n * b.dtype.itemsize, 2 * k * n * 4)
    return _call(
        functools.partial(_by_group_kernel, tm=tm), "grouped_matmul_by_group", (m // tm + g - 1,),
        [
            pl.BlockSpec((tm, k), lambda v, group, tile, *_: (tile[v], 0)),
            pl.BlockSpec((tm, n), lambda v, group, tile, *_: (tile[v], 0)),
        ],
        pl.BlockSpec((None, k, n), lambda v, group, *_: (group[v], 0, 0)),
        jax.ShapeDtypeStruct((g, k, n), F32), vmem, plan, (a, b),
    )


def grouped(a, w, sizes, form: str = ROWS, carrier: str = KERNEL):
    """The ``form`` product by ``carrier``: this module's kernel, or
    ``ragged_dot`` (``"xla"``), the oracle."""
    if carrier == XLA:
        if form == ROWS:
            return jax.lax.ragged_dot(a, w, sizes, preferred_element_type=F32)
        return jax.lax.ragged_dot_general(a, w, sizes, _ROWS_BY_GROUP, preferred_element_type=F32)
    return (rows if form == ROWS else by_group)(a, w, sizes)
