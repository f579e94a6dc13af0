"""``ops/delta_scan.py``: the delta rule's loop over chunks as kernels, in
interpret mode against the ``lax.scan`` they take the place of on a TPU
(forward, the six cotangents of the loop and, through them, the five of
``delta_rule_chunked``); which carrier runs where; and, compiled for a
described v5e, both kernels at the stream cell's real size.  Values and
bits, never a time.

The topology is described inside a fixture, never at import (only one
process may load the TPU's library at a time)."""

import base64
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.reduce import stream_scopes
from dragonfly2_tpu.models import stream
from dragonfly2_tpu.ops import delta_scan

R, HK, G, N, C, DK, DV = 2, 2, 2, 4, 16, 128, 128
NAMES = ["u", "w", "qp", "attn", "kt", "keep"]


def _inputs(dtype, seed=0, n=N):
    """The six arrays as ``delta_rule_chunked`` hands them over: ``u`` and
    ``keep`` float32, the rest in the operands' type."""
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.normal(size=s).astype(np.float32)
    return (
        jnp.asarray(f(R, HK, G, n, C, DV)),
        jnp.asarray(0.1 * f(R, HK, G, n, C, DK), dtype),
        jnp.asarray(0.1 * f(R, HK, G, n, C, DK), dtype),
        jnp.asarray(0.1 * f(R, HK, G, n, C, C), dtype),
        jnp.asarray(0.1 * f(R, HK, G, n, C, DK), dtype),
        jnp.asarray(rng.uniform(0.5, 1.0, size=(R, HK, G, n)).astype(np.float32)),
    )


def _weights(shape, seed=9):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape).astype(np.float32))


def _grads(dtype, carrier):
    args = _inputs(dtype)
    ct = _weights((R, N * C, HK, G, DV))
    loss = lambda *xs: jnp.sum(delta_scan.chunk_scan(*xs, carrier) * ct)
    return jax.grad(loss, argnums=tuple(range(6)))(*args)


@pytest.fixture(scope="module")
def grads():
    return {
        (dtype, carrier): _grads(dtype, carrier)
        for dtype in (jnp.float32, jnp.bfloat16)
        for carrier in (delta_scan.XLA, delta_scan.KERNEL)
    }


@pytest.mark.parametrize("carrier", [delta_scan.XLA, delta_scan.KERNEL])
def test_no_delta_correction_is_a_correction_of_noughts(carrier):
    """``w`` None (the SSD's chunks: ``v_new = u``) is ``w`` all noughts,
    forward and backward, on either carrier: the scan leaves the product
    out, the kernels are handed the noughts."""
    u, w, *rest = _inputs(jnp.float32)
    ct = _weights((R, N * C, HK, G, DV))
    loss = lambda w, *xs: jnp.sum(delta_scan.chunk_scan(xs[0], w, *xs[1:], carrier) * ct)
    without = jax.value_and_grad(lambda *xs: loss(None, *xs), argnums=tuple(range(5)))(u, *rest)
    noughts = jax.value_and_grad(lambda *xs: loss(jnp.zeros_like(w), *xs), argnums=tuple(range(5)))(u, *rest)
    np.testing.assert_allclose(without[0], noughts[0], rtol=1e-6)
    for a, b in zip(without[1], noughts[1]):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6 * float(np.abs(b).max()))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_forward_kernel_is_the_scan(dtype):
    """The same products of the same operands in the same order, a head at
    a time where the scan has them side by side: float32 within 2e-6,
    bfloat16 bit for bit."""
    args = _inputs(dtype)
    want = delta_scan.chunk_scan(*args, delta_scan.XLA)
    got = delta_scan.chunk_scan(*args, delta_scan.KERNEL)
    assert got.shape == want.shape == (R, N * C, HK, G, DV) and got.dtype == jnp.float32
    if dtype == jnp.bfloat16:
        assert bool((got == want).all())
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    kept = delta_scan._forward(*args, states=True)
    assert bool((kept[0] == got).all())
    assert kept[1].shape == (R, HK, G, N, DK, DV) and not bool(kept[1][:, :, :, 0].any())


@pytest.mark.parametrize("name", NAMES)
def test_each_cotangent_of_the_loop_in_float32(grads, name):
    """Against ``jax.grad`` of the ``lax.scan``: the same sums, ``keep``'s
    summed over the state in another order."""
    i = NAMES.index(name)
    want, got = grads[jnp.float32, delta_scan.XLA][i], grads[jnp.float32, delta_scan.KERNEL][i]
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("name", NAMES)
def test_each_cotangent_of_the_loop_in_bfloat16(grads, name):
    """Operands in bfloat16, sums in float32, on both sides; the scan's
    derivative also rounds the cotangent of ``v_new`` to bfloat16 on its
    way from one product to the next and the kernel keeps it float32, so
    the two differ by that rounding: two bfloat16 steps of the largest
    value (2^-7 of it) cover it."""
    i = NAMES.index(name)
    want, got = grads[jnp.bfloat16, delta_scan.XLA][i], grads[jnp.bfloat16, delta_scan.KERNEL][i]
    assert got.shape == want.shape and got.dtype == want.dtype
    want, got = want.astype(jnp.float32), got.astype(jnp.float32)
    np.testing.assert_allclose(got, want, rtol=0, atol=2.0 ** -7 * float(jnp.abs(want).max()))
    exact = grads[jnp.float32, delta_scan.XLA][i]
    assert float(jnp.abs(got - exact).max()) < 0.03 * float(jnp.abs(exact).max())


def _rule_inputs(dtype, l=64, hk=2, grp=2, seed=3):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    l2 = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    dst = jnp.asarray(np.repeat(rng.integers(0, 50, size=(2, 8)), [3, 13, 1, 15, 16, 7, 8, 1], axis=1))
    start, seg, _ = stream.segments(dst.reshape(-1), l)
    return (
        (l2(f(2, l, hk, DK)) * DK ** -0.5).astype(dtype), l2(f(2, l, hk, DK)).astype(dtype),
        f(2, l, hk, grp, DV).astype(dtype), -jax.nn.softplus(f(2, l, hk, grp)),
        jax.nn.sigmoid(f(2, l, hk, grp)),
    ), (start, seg)


@pytest.fixture(scope="module")
def rule_grads():
    """``delta_rule_chunked`` (float32, chunks of 16, segments that start
    inside chunks, at a chunk's first token and one record long) through
    each carrier: the output and its five cotangents."""
    args, (start, seg) = _rule_inputs(jnp.float32)
    ct = _weights((2, 64, 2, 2, DV))
    out = {}
    for carrier in (delta_scan.XLA, delta_scan.KERNEL):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(delta_scan, "scan_carrier", lambda *a, carrier=carrier: carrier)
            run = lambda *xs: stream.delta_rule_chunked(*xs, start, seg, 16, jnp.float32)
            out[carrier] = (run(*args), jax.grad(lambda *xs: jnp.sum(run(*xs) * ct), argnums=(0, 1, 2, 3, 4))(*args))
    return out


def test_the_rule_through_the_kernel_is_the_recurrence(rule_grads):
    args, (start, _) = _rule_inputs(jnp.float32)
    q, k, v, g, beta = args
    spread = lambda a: jnp.repeat(a, 2, axis=2)          # a key head serves G value heads
    want = stream.delta_rule_recurrent(
        spread(q), spread(k), v.reshape(2, 64, 4, DV), g.reshape(2, 64, 4), beta.reshape(2, 64, 4), start
    ).reshape(2, 64, 2, 2, DV)
    np.testing.assert_allclose(rule_grads[delta_scan.KERNEL][0], want, rtol=0, atol=2e-5)
    np.testing.assert_allclose(
        rule_grads[delta_scan.KERNEL][0], rule_grads[delta_scan.XLA][0], rtol=0, atol=2e-6
    )


@pytest.mark.parametrize("name", ["q", "k", "v", "g", "beta"])
def test_each_cotangent_of_the_rule_through_the_kernels(rule_grads, name):
    i = ["q", "k", "v", "g", "beta"].index(name)
    want, got = rule_grads[delta_scan.XLA][1][i], rule_grads[delta_scan.KERNEL][1][i]
    assert got.shape == want.shape and bool(jnp.abs(want).max() > 0)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_a_reset_leaves_nothing_of_the_earlier_state(dtype):
    """A segment that starts with chunk 1 reaches the loop as ``keep``,
    ``w`` and ``qp`` nought there: the chunk reads no state and passes
    none on.  What chunks 1 to 3 give is then, bit for bit, what they
    give after another chunk 0, and no input of chunk 0 has a gradient
    from them (chunk 2's have)."""
    cut = lambda a, at: a.at[:, :, :, at].set(0)
    reset = lambda args: (args[0], cut(args[1], 1), cut(args[2], 1), *args[3:5], cut(args[5], 1))
    a, b = reset(_inputs(dtype, 0)), reset(_inputs(dtype, 1))
    mixed = tuple(jnp.concatenate([y[:, :, :, :1], x[:, :, :, 1:]], axis=3) for x, y in zip(a, b))
    later = lambda o: o[:, C:]                            # chunks 1, 2, 3
    one = later(delta_scan.chunk_scan(*a, delta_scan.KERNEL))
    other = later(delta_scan.chunk_scan(*mixed, delta_scan.KERNEL))
    assert bool((one == other).all()) and bool(jnp.abs(one).max() > 0)
    ct = _weights(one.shape)
    loss = lambda *xs: jnp.sum(later(delta_scan.chunk_scan(*xs, delta_scan.KERNEL)) * ct)
    for name, grad in zip(NAMES, jax.grad(loss, argnums=tuple(range(6)))(*a)):
        assert not bool(grad[:, :, :, 0].any()), name
        assert bool(grad[:, :, :, 2].any()), name


@pytest.mark.parametrize("dtype,dk,dv,chunk,on_tpu", [
    (jnp.bfloat16, 128, 128, 64, delta_scan.KERNEL), (jnp.bfloat16, 256, 128, 16, delta_scan.KERNEL),
    (jnp.float32, 128, 128, 64, delta_scan.XLA), (jnp.bfloat16, 32, 32, 16, delta_scan.XLA),
    (jnp.bfloat16, 128, 192, 64, delta_scan.XLA), (jnp.bfloat16, 128, 128, 8, delta_scan.XLA),
], ids=["cell", "wider-keys-shorter-chunks", "float32-operands", "tier-1s-heads", "dv-no-lane-groups",
        "a-chunk-under-a-tile"])
def test_the_carrier_is_read_from_the_backend_and_the_heads(dtype, dk, dv, chunk, on_tpu, monkeypatch):
    assert delta_scan.scan_carrier(dtype, dk, dv, chunk) == delta_scan.XLA      # tier-1 runs on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert delta_scan.scan_carrier(dtype, dk, dv, chunk) == on_tpu


def test_heads_the_kernel_does_not_take_go_through_the_scan_and_say_nothing(monkeypatch):
    """On a TPU too: ``delta_rule_chunked`` with 32-wide heads traces to
    the ``lax.scan`` (today's operations, no Mosaic call) without a
    warning; with the cell's heads and bfloat16 it traces to the kernels."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rng = np.random.default_rng(0)

    def traced(d, dtype):
        f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
        start, seg, _ = stream.segments(jnp.zeros(32, jnp.int32), 32)
        run = lambda q, k, v, g, beta: stream.delta_rule_chunked(q, k, v, g, beta, start, seg, 16, dtype)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            return str(jax.make_jaxpr(run)(
                f(1, 32, 1, d).astype(dtype), f(1, 32, 1, d).astype(dtype), f(1, 32, 1, 2, d).astype(dtype),
                -jnp.abs(f(1, 32, 1, 2)), jax.nn.sigmoid(f(1, 32, 1, 2)),
            ))

    small = traced(32, jnp.bfloat16)
    assert "pallas_call" not in small and "scan" in small
    assert "pallas_call" not in traced(128, jnp.float32)
    cell = traced(128, jnp.bfloat16)
    assert "pallas_call" in cell and "delta_scan_fwd" in cell and "scan[" not in cell


# -- compiled for a described v5e, at the cell's size ---------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _cell_shapes(sharding=None):
    r, hk, g, n, c, dk, dv = 1, 16, 2, 64, 64, 128, 128
    shape = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=sharding)
    bf = jnp.bfloat16
    return (
        shape((r, hk, g, n, c, dv), jnp.float32), shape((r, hk, g, n, c, dk), bf),
        shape((r, hk, g, n, c, dk), bf), shape((r, hk, g, n, c, c), bf),
        shape((r, hk, g, n, c, dk), bf), shape((r, hk, g, n), jnp.float32),
    ), shape((r, n * c, hk, g, dv), jnp.float32)


def _both_passes(ct, *args):
    with jax.named_scope("stream/gdn/scan"):
        o, pull = jax.vjp(lambda *xs: delta_scan.chunk_scan(*xs, delta_scan.KERNEL), *args)
        return o, pull(ct)


def test_both_kernels_compile_for_a_v5e_at_the_cells_size(one_chip, monkeypatch):
    """One row of the cell: R 1, Hk 16, G 2, N 64, C 64, dk = dv = 128,
    bfloat16.  The compiled text holds the two Mosaic calls under the
    scan's scope and no ``while``; the states kept for the backward are the
    program's only large temporary (134 MB a row)."""
    monkeypatch.setattr(delta_scan, "_interpret", lambda: False)
    args, ct = _cell_shapes(one_chip)
    compiled = jax.jit(_both_passes).lower(ct, *args).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line and " custom-call(" in line]
    names = sorted(re.search(r'op_name="([^"]*)"', line).group(1) for line in calls)
    assert [stream_scopes.scope_of(name) for name in names] == ["gdn/scan", "gdn/scan"]     # gdn_scan_share's join
    assert "delta_scan_fwd" in names[0] and "transpose(" not in names[0]
    assert "delta_scan_bwd" in names[1] and "transpose(" in names[1]
    assert " while(" not in text
    states = 32 * 64 * 128 * 128 * 4
    assert states <= compiled.memory_analysis().temp_size_in_bytes < 1.5 * states


def test_the_kernels_bodies_hold_no_source_path(monkeypatch):
    """A Mosaic call's serialized body is part of the program's cache key:
    with a file's path in it two checkouts of the same code are two
    programs (PERF.md section 6, PR 29)."""
    monkeypatch.setattr(delta_scan, "_interpret", lambda: False)
    args, ct = _cell_shapes()
    text = jax.jit(_both_passes).trace(ct, *args).lower(lowering_platforms=("tpu",)).as_text()
    bodies = [base64.b64decode(b) for b in re.findall(r"body\\22: \\22([A-Za-z0-9+/=]+)\\22", text)]
    assert len(bodies) == 2
    for body in bodies:
        assert b".py" not in body and b"delta_scan" not in body.replace(b"delta_scan_", b"")
