"""Pallas TPU kernels: the delta rule's loop over chunks with the carried
state held on the chip.

``models/stream.py::delta_rule_chunked`` turns a row of L tokens into N
chunks of C and, per value head and chunk, the arrays ``u, w, qp, attn,
kt, keep`` (segment resets already folded into them).  What is left is a
recurrence over the chunks, here ``chunk_scan``; per head, for chunk n:

    s_in  = S.astype(dtype)
    v_new = u[n] - w[n] @ s_in                    # float32 accumulation
    o[n]  = qp[n] @ s_in + attn[n] @ v_new.astype(dtype)
    S     = S * keep[n] + kt[n].T @ v_new.astype(dtype)

As a ``lax.scan`` each turn is ~ten small device operations around 1.2 us
of arithmetic, and the float32 state ``[heads, dk, dv]`` goes through HBM
every turn (PERF.md section 5).  The kernels walk the chunks as the last,
sequential axis of their grid with ``S`` (forward) or its cotangent
(backward) in a VMEM scratch from the first chunk to the last, ``_HEADS``
heads a grid step; they read the six arrays where ``delta_rule_chunked``
left them (``[R, Hk, G, N, C, .]``, Hk and G apart: merged outside, the
reshape stands between XLA's producers and the call and keeps them from
fusing) and write ``o`` head by head, ``[R, Hk, G, L, dv]``: the
transpose to ``[R, L, Hk, G, dv]`` that follows is a choice of layout to
XLA, not a copy, and the gated norm after it reads whole tiles (written
token by token, ``[L, Hk * G * dv]``, the array reaches the norm as
``[.., 2, 128]`` tiles and the norm pays for it: PERF.md section 6).

- forward: also writes the state each chunk started from, float32, which
  is all the backward keeps beside the inputs;
- backward (``jax.custom_vjp`` over the loop alone): chunks in reverse,
  ``v_new`` made again from ``u, w`` and the kept state (one product),
  the seven cotangents with operands in ``dtype`` and float32
  accumulation, as the derivative of the ``lax.scan`` has them.

**Which carrier runs** is read from what the code can see
(``scan_carrier``): the kernels on a TPU where the operands are bfloat16,
the head widths whole lane groups and the chunk whole bfloat16 tiles,
the ``lax.scan`` anywhere else.  ``chunk_scan`` takes the carrier's name,
so both are one code path to the caller and the ``lax.scan`` stays as the
oracle the kernels are tested against (interpret mode,
``tests/test_delta_scan.py``).
"""

from __future__ import annotations

import contextlib
import functools
import math
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_LANES = 128
_TILE_ROWS = 16    # of bfloat16
# Heads a grid step holds (a step costs ~0.35 us whatever it does): at the
# cell's shapes its blocks, in flight twice, are 3 MiB forward and 4.5
# backward.
_HEADS = 8

KERNEL, XLA = "kernel", "xla"


def scan_carrier(dtype, dk: int, dv: int, chunk: int) -> str:
    """``"kernel"`` where the Pallas kernels carry the state over chunks of
    ``chunk`` tokens for heads ``dk`` x ``dv`` with operands of ``dtype``,
    ``"xla"`` where the ``lax.scan`` does: the one test
    ``delta_rule_chunked`` and the trainer's span both ask."""
    fits = (
        jnp.dtype(dtype) == jnp.bfloat16
        and dk % _LANES == 0 and dv % _LANES == 0 and chunk % _TILE_ROWS == 0
    )
    return KERNEL if jax.default_backend() == "tpu" and fits else XLA


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=1)
def _no_user_frame():
    """A traceback none of whose frames jax takes for the user's: a pool
    thread's stack is the standard library's alone."""
    from jax._src.lib import xla_client

    with ThreadPoolExecutor(1) as pool:
        return pool.submit(xla_client.Traceback.get_traceback).result()


def _without_locations():
    """Traces what is inside with no source file on its equations: a Mosaic
    call's serialized body is part of the program's cache key, and a
    file's path in it makes two checkouts of the same code two programs
    (PERF.md section 6, PR 29).  The name stack (``op_name``) stays."""
    try:
        from jax._src import source_info_util

        return source_info_util.user_context(_no_user_frame())
    except (ImportError, AttributeError):      # another jax: locations stay
        return contextlib.nullcontext()


# -- the lax.scan ------------------------------------------------------------------------


def _scan_xla(u, w, qp, attn, kt, keep):
    r, hk, grp, n, c, dv = u.shape
    dtype = w.dtype

    def body(s, xs):
        u_c, w_c, qp_c, attn_c, kt_c, keep_c = xs
        s_in = s.astype(dtype)
        v_new = u_c - jnp.einsum("rhgck,rhgkv->rhgcv", w_c, s_in, preferred_element_type=F32)
        o = jnp.einsum("rhgck,rhgkv->rhgcv", qp_c, s_in, preferred_element_type=F32)
        v_in = v_new.astype(dtype)
        o = o + jnp.einsum("rhgcs,rhgsv->rhgcv", attn_c, v_in, preferred_element_type=F32)
        s = s * keep_c[..., None, None] + jnp.einsum(
            "rhgck,rhgcv->rhgkv", kt_c, v_in, preferred_element_type=F32
        )
        return s, o

    lead = lambda x: jnp.moveaxis(x, 3, 0)               # chunk axis first
    _, o = jax.lax.scan(
        body, jnp.zeros((r, hk, grp, w.shape[-1], dv), F32),
        (lead(u), lead(w), lead(qp), lead(attn), lead(kt), lead(keep)),
    )
    # [N, R, Hk, G, C, dv] -> [R, L, Hk, G, dv]
    return jnp.transpose(o, (1, 0, 4, 2, 3, 5)).reshape(r, n * c, hk, grp, dv)


# -- the kernels ---------------------------------------------------------------------------


def _mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=F32)


def _merged(x):
    """[hk, G, ., .] -> [hk * G, ., .]: a grid step's heads as one axis."""
    return x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])


def _heads(ref, at=0):
    """A block [1, hk, G, 1 | N, ., .] of a ref as [hk * G, ., .] (``at``
    the chunk, where the block holds the row's)."""
    return _merged(ref[0, :, :, at])


def _put(ref, x, at=0):
    ref[0, :, :, at] = x.reshape(ref.shape[1], ref.shape[2], *x.shape[1:])


def _fwd_kernel(u_ref, w_ref, qp_ref, attn_ref, kt_ref, keep_ref, o_ref, *rest):
    """One chunk of ``hk * G`` heads: refs [1, hk, G, 1, C, .], ``keep``
    [1, hk, G, N, dv] (a head's factor along the lanes), ``o`` [1, hk, G,
    C, dv]; ``rest`` is the kept states' block, if asked for, then the
    carried state [hk * G, dk, dv]."""
    s_ref = rest[-1]
    n = pl.program_id(2)
    dtype = w_ref.dtype

    @pl.when(n == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    s = s_ref[...]
    if len(rest) == 2:
        _put(rest[0], s)
    s_in = s.astype(dtype)
    v_in = (_heads(u_ref) - _mm("hck,hkv->hcv", _heads(w_ref), s_in)).astype(dtype)
    o = _mm("hck,hkv->hcv", _heads(qp_ref), s_in) + _mm("hcs,hsv->hcv", _heads(attn_ref), v_in)
    o_ref[0] = o.reshape(o_ref.shape[1:])
    s_ref[...] = s * _heads(keep_ref, pl.ds(n, 1)) + _mm("hck,hcv->hkv", _heads(kt_ref), v_in)


def _bwd_kernel(
    u_ref, w_ref, qp_ref, attn_ref, kt_ref, keep_ref, s_ref, do_ref,
    du_ref, dw_ref, dqp_ref, dattn_ref, dkt_ref, dkeep_ref, ds_ref,
):
    """The same chunk on the way back (the grid walks the chunks from the
    last): ``s_ref`` the state it started from, ``do`` [1, hk, G, C, dv],
    ``ds_ref`` the carried cotangent of the state the chunk leaves,
    ``dkeep`` [1, hk, G, N, dv] summed over dk alone (the lanes are summed
    outside)."""
    i = pl.program_id(2)
    n = pl.num_programs(2) - 1 - i
    dtype = w_ref.dtype

    @pl.when(i == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    do = _merged(do_ref[0]).astype(dtype)
    s = _heads(s_ref)
    s_in = s.astype(dtype)
    ds = ds_ref[...]
    ds_in = ds.astype(dtype)
    w, qp, kt = _heads(w_ref), _heads(qp_ref), _heads(kt_ref)
    v_in = (_heads(u_ref) - _mm("hck,hkv->hcv", w, s_in)).astype(dtype)
    dv_new = _mm("hcs,hcv->hsv", _heads(attn_ref), do) + _mm("hck,hkv->hcv", kt, ds_in)
    _put(du_ref, dv_new)
    dv_in = dv_new.astype(dtype)
    _put(dw_ref, (-_mm("hcv,hkv->hck", dv_in, s_in)).astype(dtype))
    _put(dqp_ref, _mm("hcv,hkv->hck", do, s_in).astype(dtype))
    _put(dattn_ref, _mm("hcv,hsv->hcs", do, v_in).astype(dtype))
    _put(dkt_ref, _mm("hcv,hkv->hck", v_in, ds_in).astype(dtype))
    _put(dkeep_ref, jnp.sum(s * ds, axis=1, keepdims=True), pl.ds(n, 1))
    ds_ref[...] = (
        ds * _heads(keep_ref, pl.ds(n, 1))
        + _mm("hck,hcv->hkv", qp, do) - _mm("hck,hcv->hkv", w, dv_in)
    )


def _key_heads_a_step(hk: int, grp: int) -> int:
    """Key heads (each with its ``grp`` value heads) a grid step takes:
    ``_HEADS`` value heads if the row has them, and at least one key's."""
    return max(h for h in range(1, hk + 1) if hk % h == 0 and (h == 1 or h * grp <= _HEADS))


def _per_chunk(shape, hkb: int, chunk_of):
    """The block of an array [R, Hk, G, N, ., .]: ``hkb`` key heads' value
    heads at the chunk the grid's last index names through ``chunk_of``.
    The arrays go in and come out with Hk and G apart, as
    ``delta_rule_chunked`` has them: merged outside, the reshape stands
    between XLA's producers and consumers and their fusions (a broadcast
    over G is then written out whole)."""
    _, _, grp, _, c, d = shape
    return pl.BlockSpec((1, hkb, grp, 1, c, d), lambda r, h, n: (r, h, 0, chunk_of(n), 0, 0))


def _of_tokens(grp: int, c: int, dv: int, hkb: int, chunk_of):
    """The block of ``o``'s (and its cotangent's) [R, Hk, G, L, dv]: the
    same heads' tokens of that chunk."""
    return pl.BlockSpec((1, hkb, grp, c, dv), lambda r, h, n: (r, h, 0, chunk_of(n), 0))


def _of_row(shape, hkb: int):
    """The block of ``keep``'s [R, Hk, G, N, dv]: the row's chunks, fetched
    once a group of heads."""
    return pl.BlockSpec((1, hkb, *shape[2:]), lambda r, h, n: (r, h, 0, 0, 0))


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch, operands):
    block_bytes = sum(
        2 * jnp.dtype(a.dtype).itemsize * math.prod(s.block_shape)
        for s, a in zip([*in_specs, *out_specs], [*operands, *out_shape])
    )
    with _without_locations():
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=max(32, 2 * block_bytes // 2**20) * 2**20,
            ),
            interpret=_interpret(),
            name=name,
        )(*operands)


def _keep_lanes(keep, dv: int):
    """[R, Hk, G, N] -> [R, Hk, G, N, dv]: a head's factor along the lanes,
    so that the kernel multiplies a [dk, dv] state by a row and reads no
    scalar."""
    return jnp.broadcast_to(keep[..., None], (*keep.shape, dv))


def _forward(u, w, qp, attn, kt, keep, states: bool):
    r, hk, grp, n, c, dv = u.shape
    dk = w.shape[-1]
    hkb = _key_heads_a_step(hk, grp)
    per_chunk = (u, w, qp, attn, kt)
    lanes = _keep_lanes(keep, dv)
    out_specs = [_of_tokens(grp, c, dv, hkb, lambda n: n)]
    out_shape = [jax.ShapeDtypeStruct((r, hk, grp, n * c, dv), F32)]
    if states:
        out_shape.append(jax.ShapeDtypeStruct((r, hk, grp, n, dk, dv), F32))
        out_specs.append(_per_chunk(out_shape[1].shape, hkb, lambda n: n))
    o, *kept = _call(
        _fwd_kernel, "delta_scan_fwd", (r, hk // hkb, n),
        [*(_per_chunk(a.shape, hkb, lambda n: n) for a in per_chunk), _of_row(lanes.shape, hkb)],
        out_specs, out_shape, [pltpu.VMEM((hkb * grp, dk, dv), F32)],
        [*per_chunk, lanes],
    )
    return (jnp.transpose(o, (0, 3, 1, 2, 4)), *kept)


def _backward(u, w, qp, attn, kt, keep, kept, do):
    r, hk, grp, n, c, dv = u.shape
    dk = w.shape[-1]
    hkb = _key_heads_a_step(hk, grp)
    back = lambda i: n - 1 - i
    per_chunk = (u, w, qp, attn, kt)
    lanes = _keep_lanes(keep, dv)
    outs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (*per_chunk, lanes)]
    *grads, dkeep = _call(
        _bwd_kernel, "delta_scan_bwd", (r, hk // hkb, n),
        [
            *(_per_chunk(a.shape, hkb, back) for a in per_chunk), _of_row(lanes.shape, hkb),
            _per_chunk(kept.shape, hkb, back),
            _of_tokens(grp, c, dv, hkb, back),
        ],
        [*(_per_chunk(a.shape, hkb, back) for a in per_chunk), _of_row(lanes.shape, hkb)],
        outs,
        [pltpu.VMEM((hkb * grp, dk, dv), F32)],
        [*per_chunk, lanes, kept, jnp.transpose(do, (0, 2, 3, 1, 4))],
    )
    return (*grads, dkeep.sum(-1))


@jax.custom_vjp
def _scan_kernel(u, w, qp, attn, kt, keep):
    return _forward(u, w, qp, attn, kt, keep, states=False)[0]


def _scan_kernel_fwd(u, w, qp, attn, kt, keep):
    o, kept = _forward(u, w, qp, attn, kt, keep, states=True)
    return o, (u, w, qp, attn, kt, keep, kept)


_scan_kernel.defvjp(_scan_kernel_fwd, lambda res, do: _backward(*res, do))


def chunk_scan(u, w, qp, attn, kt, keep, carrier: str = KERNEL):
    """The recurrence over a row's chunks.  ``u`` [R, Hk, G, N, C, dv]
    float32; ``w, qp, kt`` [R, Hk, G, N, C, dk] and ``attn`` [R, Hk, G, N,
    C, C] in the operands' type; ``keep`` [R, Hk, G, N] float32.  Returns
    ``o`` [R, N * C, Hk, G, dv] float32."""
    return (_scan_xla if carrier == XLA else _scan_kernel)(u, w, qp, attn, kt, keep)
