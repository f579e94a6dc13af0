"""Pallas TPU kernels: indexed row copies with many rows in flight.

The stream ranker's expert layer (``models/stream.py::routed_experts``)
moves a block of 32,768 token rows into expert order before the grouped
products and adds the products' rows back into token order after them.
XLA does both a row at a time (a gather of bfloat16 rows 1.53 ms, a
scatter-add of float32 rows 4.23 ms on the v5e at ``[32768, 2048]``).
These kernels issue the same row copies as DMAs and keep two chunks of
them in flight (1.05 and 1.3-1.5 ms there; PERF.md section 6, PR 28):

- ``gather_rows(x [T, D], rows [S]) -> [S, D]``: ``jnp.take(x, rows, 0)``.
  A grid step waits for its chunk's rows, the next chunk's on their way
  meanwhile, unpacks them and writes them out in order.
- ``add_rows(y [T, D] f32, rows [S], updates [S, D]) -> y``:
  ``y.at[rows].add(updates)`` in place.  The slots are walked in order,
  a chunk of ``_CHUNK`` at a time: the chunk's rows of ``y`` are fetched,
  the updates added on the vector units and the rows written back, the
  next chunk's fetches in flight meanwhile.  ``rows`` may repeat a row
  (a token sits in several experts' groups; a padding row names a row a
  held slot names too), and a repeat read before the write it follows
  would lose an addend.  A run of strictly ascending rows cannot repeat
  one, so the copies in flight never span a descent: a chunk with a
  descent in it (``_descents``) waits for everything before it and goes
  run by run.  Every row therefore receives its addends in slot order,
  one float32 addition each, whatever was in flight: the result is the
  sequential loop's, bit for bit, on every call.

**The layout the kernels want.**  Mosaic slices an HBM array only along
untiled leading dimensions, and XLA tiles the last two, so a ``[T, D]``
array gives up no single row.  ``pack`` therefore makes ``[T, 1, W]``
32-bit words (a row contiguous in memory): float32 rows as they are,
bfloat16 rows two columns to a word (column ``c`` beside ``c + D/2``, so
that unpacking is a shift, a mask and two lane-aligned stores).
``routed_experts`` packs ``x`` once a layer and carries its float32 sums
packed through its loop; the gather unpacks what it fetched and the add
takes its updates as the products left them, so nothing else changes
form.  ``gather_rows`` and ``add_rows`` are the same calls on plain
``[T, D]`` arrays, for tests and the chip smoke.

**Which carrier runs** is read from the backend and the row
(``row_mover``): the kernels on a TPU where a row of bfloat16 or float32
is whole 128-word lane groups, ``jnp.take`` / ``.at[].add`` anywhere
else.  The packed calls take the carrier's name, so both carriers are
one code path to their caller, and the XLA forms stay as the oracle the
kernels are tested against (interpret mode, ``tests/test_slot_rows.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# Rows a grid step of either kernel holds in VMEM (the step's own and the
# next one's, in flight): two [_CHUNK, D] float32 buffers are 2 MiB at D
# 2048.  _UNROLL row copies are started or waited for a loop turn.
_CHUNK = 128
_UNROLL = 8

KERNEL, XLA = "kernel", "xla"
_PACKED = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))


def row_mover(width: int, dtype) -> str:
    """``"kernel"`` where the Pallas kernels move a ``[*, width]`` row of
    ``dtype``, ``"xla"`` where ``jnp.take`` / ``.at[].add`` do: the one
    test ``routed_experts`` and the trainer's span both ask."""
    dtype = jnp.dtype(dtype)
    whole = (width * dtype.itemsize) % (4 * _LANES) == 0
    return KERNEL if jax.default_backend() == "tpu" and dtype in _PACKED and whole else XLA


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _each(lo, hi, fn):
    """``fn(j)`` for j in [lo, hi), ``_UNROLL`` a loop turn where the
    bounds are known (Pallas unrolls a loop whole or not at all)."""
    if isinstance(lo, int) and isinstance(hi, int) and (hi - lo) % _UNROLL == 0:
        def turn(g, _):
            for j in range(_UNROLL):
                fn(lo + g * _UNROLL + j)
            return 0

        jax.lax.fori_loop(0, (hi - lo) // _UNROLL, turn, 0)
    else:
        jax.lax.fori_loop(lo, hi, lambda j, _: (fn(j), 0)[1], 0)


def _chunk_of(n: int, chunk: int) -> int:
    chunk = min(chunk, n)
    if n % chunk:
        raise ValueError(f"{n} rows are not whole chunks of {chunk}")
    return chunk


def _vmem(chunk: int, width: int) -> int:
    """Six chunk-sized float32 buffers at most (two of the kernel's own,
    two blocks in and two out), and room to spare."""
    return max(16, 8 * chunk * width * 4 // 2**20) * 2**20


# -- the packed form ---------------------------------------------------------------------


def _pack_kernel(x_ref, o_ref):
    w = o_ref.shape[2]
    bits = lambda v: pltpu.bitcast(v.astype(jnp.float32), jnp.uint32)
    o_ref[:, 0, :] = (bits(x_ref[:, :w]) >> 16) | bits(x_ref[:, w:])


def pack(x, mover: str = KERNEL, *, chunk: int = _CHUNK):
    """``x [N, D]`` as the carrier moves it: ``[N, 1, W]`` 32-bit words for
    the kernels (float32 rows as they are; bfloat16 rows as uint32, column
    ``c`` in the low half beside ``c + D/2`` in the high), ``x`` for XLA."""
    if mover == XLA:
        return x
    n, d = x.shape
    if x.dtype == jnp.float32:
        return x.reshape(n, 1, d)
    if x.dtype != jnp.bfloat16:
        raise TypeError(f"rows of {x.dtype} are not packed")
    chunk = _chunk_of(n, chunk)
    return pl.pallas_call(
        _pack_kernel,
        grid=(n // chunk,),
        in_specs=[pl.BlockSpec((chunk, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((chunk, 1, d // 2), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1, d // 2), jnp.uint32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=_interpret(),
        name="slot_rows_pack",
    )(x)


def _unpack_into(o_ref, words):
    """[C, W] words of ``pack`` into ``o_ref [C, D]``, inside a kernel."""
    if o_ref.dtype == jnp.float32:
        o_ref[...] = words
        return
    w = words.shape[1]
    as_rows = lambda v: pltpu.bitcast(v, jnp.float32).astype(jnp.bfloat16)
    o_ref[:, :w] = as_rows(words << 16)
    o_ref[:, w:] = as_rows(words & jnp.uint32(0xFFFF0000))


def unpack(yp, mover: str = KERNEL):
    """The float32 sums the layer carried packed, back as ``[N, D]``
    (``pack``'s inverse for float32 rows; bfloat16 rows are unpacked where
    they are gathered, ``_unpack_into``)."""
    if mover == XLA:
        return yp
    return yp.reshape(yp.shape[0], yp.shape[2])


# -- gather --------------------------------------------------------------------------------


def _gather_kernel(rows_ref, x_ref, o_ref, stage, sem, *, chunk: int):
    i = pl.program_id(0)
    slot = i % 2

    def copy(row, j, k):    # x[row] -> stage[k, j]
        return pltpu.make_async_copy(x_ref.at[pl.ds(row, 1)], stage.at[k, pl.ds(j, 1)], sem.at[k])

    fetch = lambda c, j, k: copy(rows_ref[c * chunk + j], j, k)     # row j of chunk c

    @pl.when(i == 0)
    def _():
        _each(0, chunk, lambda j: fetch(0, j, 0).start())

    # The next chunk's rows are on their way while this one's are waited
    # for and written out: two chunks of row copies in flight.
    @pl.when(i + 1 < pl.num_programs(0))
    def _():
        _each(0, chunk, lambda j: fetch(i + 1, j, 1 - slot).start())

    # A wait takes one copy's size off the semaphore: any row's will do.
    _each(0, chunk, lambda j: copy(0, 0, slot).wait())
    _unpack_into(o_ref, stage[slot][:, 0, :])


def gather_packed(xp, rows, dtype, mover: str = KERNEL, *, chunk: int = _CHUNK):
    """The rows of ``xp`` (``pack``'s form of a ``dtype`` array) that
    ``rows [S]`` names, in order and unpacked: ``[S, D]`` ``dtype``."""
    if mover == XLA:
        return jnp.take(xp, rows, axis=0)
    s, w = rows.shape[0], xp.shape[2]
    d = w * 4 // jnp.dtype(dtype).itemsize
    chunk = _chunk_of(s, chunk)
    return pl.pallas_call(
        functools.partial(_gather_kernel, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s // chunk,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((chunk, d), lambda i, rows: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, chunk, 1, w), xp.dtype), pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((s, d), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_vmem(chunk, w)
        ),
        interpret=_interpret(),
        name="slot_rows_gather",
    )(rows.astype(jnp.int32), xp)


def gather_rows(x, rows, *, chunk: int = _CHUNK):
    """``jnp.take(x, rows, axis=0)`` by the kernels: ``x [T, D]``, ``rows
    [S]`` -> ``[S, D]``."""
    return gather_packed(pack(x, chunk=chunk), rows, x.dtype, chunk=chunk)


# -- add -----------------------------------------------------------------------------------


def _descents(rows, chunk: int):
    """For each chunk of ``rows``, whether a row in it is not above the
    row before it (the chunk's first against the last of the chunk
    before): only across such a descent can a row repeat."""
    down = jnp.concatenate([jnp.zeros((1,), bool), rows[1:] <= rows[:-1]])
    return down.reshape(-1, chunk).any(axis=1).astype(jnp.int32)


def _add_kernel(rows_ref, down_ref, u_ref, y_in, y_ref, buf, gsem, ssem, *, chunk: int):
    del y_in  # aliased to y_ref
    i = pl.program_id(0)
    n = pl.num_programs(0)
    slot = i % 2
    base = i * chunk

    def copy_in(row, j, k):      # y[row] -> buf[k, j]
        return pltpu.make_async_copy(y_ref.at[pl.ds(row, 1)], buf.at[k, pl.ds(j, 1)], gsem.at[k])

    def copy_out(row, j, k):
        return pltpu.make_async_copy(buf.at[k, pl.ds(j, 1)], y_ref.at[pl.ds(row, 1)], ssem.at[k])

    fetch = lambda c, j, k: copy_in(rows_ref[c * chunk + j], j, k)      # row j of chunk c
    store = lambda c, j, k: copy_out(rows_ref[c * chunk + j], j, k)
    # A wait takes one copy's size off the semaphore: any row's will do.
    fetched = lambda k: copy_in(0, 0, k).wait()
    stored = lambda k: copy_out(0, 0, k).wait()
    ascending = down_ref[i] == 0
    before_ascending = down_ref[jnp.maximum(i - 1, 0)] == 0

    @pl.when((i == 0) & ascending)
    def _():
        _each(0, chunk, lambda j: fetch(i, j, slot).start())

    @pl.when(ascending)
    def _():
        _each(0, chunk, lambda j: fetched(slot))
        buf[slot, :, 0, :] = buf[slot, :, 0, :] + u_ref[...]
        _each(0, chunk, lambda j: store(i, j, slot).start())

    # The chunk before wrote from the other half of buf: it is free, and
    # (where this chunk goes run by run) nothing is in flight, after this.
    @pl.when((i > 0) & before_ascending)
    def _():
        _each(0, chunk, lambda j: stored(1 - slot))

    @pl.when(jnp.logical_not(ascending))
    def _():
        def run(a):
            # [a, b): the longest strictly ascending run from a.
            b = jax.lax.while_loop(
                lambda b: (b < chunk) & (rows_ref[base + b] > rows_ref[base + b - 1]),
                lambda b: b + 1, a + 1,
            )
            _each(a, b, lambda j: fetch(i, j, slot).start())
            _each(a, b, lambda j: fetched(slot))

            def add(j):
                buf[slot, pl.ds(j, 1), 0, :] = buf[slot, pl.ds(j, 1), 0, :] + u_ref[pl.ds(j, 1), :]

            _each(a, b, add)
            _each(a, b, lambda j: store(i, j, slot).start())
            _each(a, b, lambda j: stored(slot))
            return b

        jax.lax.while_loop(lambda a: a < chunk, run, jnp.int32(0))

    @pl.when((i + 1 < n) & (down_ref[jnp.minimum(i + 1, n - 1)] == 0))
    def _():
        _each(0, chunk, lambda j: fetch(i + 1, j, 1 - slot).start())

    @pl.when((i == n - 1) & ascending)
    def _():
        _each(0, chunk, lambda j: stored(slot))


def add_packed(yp, rows, updates, mover: str = KERNEL, *, chunk: int = _CHUNK):
    """``yp.at[rows].add(updates)`` in place: ``yp`` float32 in ``pack``'s
    form, ``updates [S, D]`` float32 as the products left them."""
    if mover == XLA:
        return yp.at[rows].add(updates)
    s, d = rows.shape[0], yp.shape[2]
    chunk = _chunk_of(s, chunk)
    rows = rows.astype(jnp.int32)
    return pl.pallas_call(
        functools.partial(_add_kernel, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s // chunk,),
            in_specs=[
                pl.BlockSpec((chunk, d), lambda i, rows, down: (i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((2, chunk, 1, d), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(yp.shape, yp.dtype),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_vmem(chunk, d)
        ),
        interpret=_interpret(),
        name="slot_rows_add",
    )(rows, _descents(rows, chunk), updates.astype(jnp.float32), yp)


def add_rows(y, rows, updates, *, chunk: int = _CHUNK):
    """``y.at[rows].add(updates)`` by the kernel: ``y [T, D]`` float32,
    ``rows [S]``, ``updates [S, D]``."""
    return unpack(add_packed(pack(y), rows, updates, chunk=chunk))
