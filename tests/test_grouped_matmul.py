"""``ops/grouped_matmul.py``: the grouped products' kernels in interpret
mode against ``jax.lax.ragged_dot`` / ``ragged_dot_general``, the oracle,
over the group layouts the expert layer's blocks give them
(``models/stream.py::_block_plan``); the plan of visits; which carrier
runs where; and, compiled for a described v5e, both kernels at the two
stream cells' real sizes.  Values and bits, never a time.

The topology is described inside a fixture, never at import (only one
process may load the TPU's library at a time)."""

import base64
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from dragonfly2_tpu.ops import grouped_matmul as gm

F32, BF16 = jnp.float32, jnp.bfloat16
M, K, N, TM = 256, 128, 128, 64

LAYOUTS = {
    # uneven groups, one empty between two others
    "uneven": [70, 0, 100, 86],
    # empty groups first and last-but-one; the last takes the rest
    "empty-groups": [0, 0, 150, 0, 106],
    # a group edge inside every one of the four tiles of 64
    "an-edge-in-every-tile": [30, 64, 64, 64, 34],
    # a block past the held slots: every row rides the last group
    "every-row-in-the-last-group": [0, 0, 0, M],
    # one row a group, then the rest
    "single-rows": [1, 1, 1, 1, M - 4],
}


def _operands(lhs_dtype, rhs_dtype, groups, seed, by_group=False):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(size=(M, K)), lhs_dtype)
    if by_group:
        return a, jnp.asarray(rng.normal(size=(M, N)), rhs_dtype)
    return a, jnp.asarray(rng.normal(size=(groups, K, N)) * 0.1, rhs_dtype)


def _exact(x):
    return jnp.asarray(x, F32)


@pytest.fixture
def tile(monkeypatch):
    """Sets the row tile the kernels take (``tiles`` picks it from the
    shapes): ``tile(64)``."""
    return lambda tm: monkeypatch.setattr(gm, "tiles", lambda form, m, groups: tm)


def _as_the_chip(x):
    """A factor as the chip's ``ragged_dot`` multiplies it: a float32 one
    rounded to bfloat16 (one pass), exactly, in float32."""
    return x.astype(BF16).astype(F32)


@pytest.mark.parametrize("tm", [TM, M], ids=["tiles-of-64", "one-tile"])
@pytest.mark.parametrize("lhs", [BF16, F32], ids=["bfloat16-lhs", "float32-lhs"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_rows_is_ragged_dot(layout, lhs, tm, tile):
    """Against ``ragged_dot`` of the factors as the chip multiplies them
    (exact in float32 on the CPU), to a sum's order."""
    sizes = jnp.asarray(LAYOUTS[layout], jnp.int32)
    a, w = _operands(lhs, BF16, sizes.shape[0], seed=len(layout))
    tile(tm)
    got = gm.rows(a, w, sizes)
    want = jax.lax.ragged_dot(_as_the_chip(a), _exact(w), sizes, preferred_element_type=F32)
    assert got.shape == want.shape == (M, N) and got.dtype == F32
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("tm", [TM, M], ids=["tiles-of-64", "one-tile"])
@pytest.mark.parametrize("rhs", [BF16, F32], ids=["bfloat16-rhs", "float32-rhs"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_by_group_is_ragged_dot_general(layout, rhs, tm, tile):
    """``[G, K, N]``, each group's rows of ``a`` transposed times ``b``'s;
    an empty group's exactly nought."""
    sizes = jnp.asarray(LAYOUTS[layout], jnp.int32)
    a, b = _operands(BF16, rhs, sizes.shape[0], seed=len(layout) + 1, by_group=True)
    tile(tm)
    got = gm.by_group(a, b, sizes)
    want = jax.lax.ragged_dot_general(
        _exact(a), _as_the_chip(b), sizes, gm._ROWS_BY_GROUP, preferred_element_type=F32
    )
    assert got.shape == want.shape == (sizes.shape[0], K, N) and got.dtype == F32
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6 * float(jnp.abs(want).max()))
    for g, size in enumerate(LAYOUTS[layout]):
        assert bool((got[g] == 0).all()) == (size == 0), g


def test_rows_past_the_groups_read_nought(tile):
    """``ragged_dot`` leaves a row no group holds at nought; so does the
    kernel, where the groups end inside a tile and where a whole tile lies
    past them."""
    sizes = jnp.asarray([40, 0, 50], jnp.int32)              # 90 of 256 rows
    a, w = _operands(BF16, BF16, 3, seed=3)
    tile(TM)
    got = gm.rows(a, w, sizes)
    want = jax.lax.ragged_dot(_exact(a), _exact(w), sizes, preferred_element_type=F32)
    assert not bool(got[90:].any()) and not bool(want[90:].any())
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6 * float(jnp.abs(want).max()))


def test_a_float32_factor_is_rounded_once_and_no_further():
    """What the chip's ``ragged_dot`` computes of a float32 factor against a
    bfloat16 one is the product of its bfloat16 rounding (PERF.md section
    6): the kernel's error against the exact product is that
    rounding's, no smaller and no larger."""
    sizes = jnp.asarray(LAYOUTS["uneven"], jnp.int32)
    a, w = _operands(F32, BF16, 4, seed=9)
    exact = jax.lax.ragged_dot(a, _exact(w), sizes, preferred_element_type=F32)
    rounded = jax.lax.ragged_dot(_as_the_chip(a), _exact(w), sizes, preferred_element_type=F32)
    got = gm.rows(a, w, sizes)
    scale = float(jnp.abs(exact).max())
    assert float(jnp.abs(got - rounded).max()) < 1e-6 * scale < float(jnp.abs(got - exact).max()) < 2.0 ** -8 * scale


def _plan(layout, tm, every_group, to_the_end):
    sizes = jnp.asarray(LAYOUTS[layout], jnp.int32)
    group, tile, fresh, offsets, real = (np.asarray(x) for x in gm._visits(sizes, M, tm, every_group, to_the_end))
    return sizes, group, tile, fresh, offsets, int(real[0])


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("form", [gm.ROWS, gm.BY_GROUP])
def test_the_plan_visits_every_tile_group_by_group(layout, form):
    """As many visits as the grid has, ``M / tm + G - 1``; the computing
    ones first, each group's tiles in order, so that a tile (``rows``) or a
    group (``by_group``) is one run; every tile a group holds a row of is
    visited by it; the rest repeat the last computing visit."""
    every = form == gm.BY_GROUP
    sizes, group, tile, fresh, offsets, real = _plan(layout, TM, every_group=every, to_the_end=not every)
    g = sizes.shape[0]
    assert group.shape == tile.shape == fresh.shape == (M // TM + g - 1,)
    assert 0 < real <= M // TM + g - 1
    assert (group[:real] == np.sort(group[:real])).all()
    assert (group[real:] == group[real - 1]).all() and (tile[real:] == tile[real - 1]).all()
    seen = set(zip(group[:real].tolist(), tile[:real].tolist()))
    assert len(seen) == real                                   # no visit twice
    for gi, size in enumerate(np.asarray(sizes).tolist()):
        lo, hi = int(offsets[gi]), int(offsets[gi + 1])
        mine = {t for gg, t in seen if gg == gi}
        if size:
            assert mine >= set(range(lo // TM, (hi + TM - 1) // TM)), gi
        else:
            assert len(mine) == (1 if every else (M // TM - lo // TM if gi == g - 1 and lo < M else 0))
    run = group if every else tile
    firsts = [v for v in range(real) if fresh[v]]
    assert firsts == [v for v in range(real) if v == 0 or run[v] != run[v - 1]]
    assert len(set(run[:real].tolist())) == len(firsts)        # each run visited once


def test_an_edge_in_every_tile_takes_every_extra_visit():
    _, _, _, _, _, real = _plan("an-edge-in-every-tile", TM, every_group=False, to_the_end=True)
    assert real == M // TM + 5 - 1
    _, _, _, _, _, real = _plan("every-row-in-the-last-group", TM, every_group=False, to_the_end=True)
    assert real == M // TM


@pytest.mark.parametrize("form,m,groups,tm", [
    (gm.ROWS, 32768, 16, 256), (gm.BY_GROUP, 32768, 16, 512), (gm.ROWS, 32768, 32, 256),
    (gm.BY_GROUP, 32768, 32, 256), (gm.ROWS, 32768, 64, 128), (gm.BY_GROUP, 512, 4, 32),
    (gm.ROWS, 96, 4, 8), (gm.ROWS, 100, 4, 100),
], ids=["16k-cell-rows", "16k-cell-by-group", "4k-cell-rows", "4k-cell-by-group", "more-groups",
        "short-block", "tier-1s-block", "rows-no-tile-divides"])
def test_the_row_tile_follows_the_form_and_the_group_count(form, m, groups, tm):
    assert gm.tiles(form, m, groups) == tm
    assert m % tm == 0


@pytest.mark.parametrize("form,k,n,dtypes,on_tpu", [
    (gm.ROWS, 2560, 768, (BF16, BF16), gm.KERNEL), (gm.ROWS, 1536, 2560, (F32, BF16), gm.KERNEL),
    (gm.BY_GROUP, 2560, 768, (BF16, F32), gm.KERNEL), (gm.BY_GROUP, 768, 2560, (BF16, BF16), gm.KERNEL),
    (gm.ROWS, 40, 16, (BF16, BF16), gm.XLA), (gm.ROWS, 2560, 768, (BF16, F32), gm.XLA),
    (gm.BY_GROUP, 2560, 768, (F32, F32), gm.XLA), (gm.ROWS, 2560, 768, (jnp.float16, BF16), gm.XLA),
], ids=["gate", "dxb", "dW_gate", "dW_down-bfloat16", "tier-1s-widths", "float32-weights",
        "float32-both-by-group", "float16"])
def test_the_carrier_is_read_from_the_backend_the_widths_and_the_types(form, k, n, dtypes, on_tpu, monkeypatch):
    assert gm.grouped_carrier(form, k, n, 16, dtypes) == gm.XLA      # tier-1 runs on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gm.grouped_carrier(form, k, n, 16, dtypes) == on_tpu


def test_the_xla_carrier_is_ragged_dot():
    sizes = jnp.asarray(LAYOUTS["uneven"], jnp.int32)
    a, w = _operands(BF16, BF16, 4, seed=1)
    text = str(jax.make_jaxpr(lambda a, w, s: gm.grouped(a, w, s, gm.ROWS, gm.XLA))(a, w, sizes))
    assert "pallas_call" not in text and "ragged_dot_general" in text
    text = str(jax.make_jaxpr(lambda a, w, s: gm.grouped(a, w, s, gm.ROWS, gm.KERNEL))(a, w, sizes))
    assert "pallas_call" in text and "ragged_dot" not in text


# -- compiled for a described v5e, at the cells' sizes ------------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu from describing a chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# (form, K, N, the other factor's dtype) of the expert block's products at
# each cell's widths, as the layer hands them over: gate and up (and
# ``dh``), down, ``dxb``, the weights' two kinds of gradient; and the
# float32 factors the kernels also take.
CELLS = {"16k": (2560, 768, 16), "4k": (2048, 512, 32)}


def _products(d, f):
    return [(gm.ROWS, d, f, BF16), (gm.ROWS, f, d, BF16), (gm.ROWS, 2 * f, d, BF16),
            (gm.BY_GROUP, d, f, BF16), (gm.BY_GROUP, f, d, BF16),
            (gm.ROWS, 2 * f, d, F32), (gm.BY_GROUP, d, f, F32)]


def _lowered(form, k, n, dtype, g, sharding):
    sds = lambda shape, t: jax.ShapeDtypeStruct(shape, t, sharding=sharding)
    t = 32768
    if form == gm.ROWS:
        return jax.jit(gm.rows).trace(sds((t, k), dtype), sds((g, k, n), BF16), sds((g,), jnp.int32))
    return jax.jit(gm.by_group).trace(sds((t, k), BF16), sds((t, n), dtype), sds((g,), jnp.int32))


@pytest.mark.parametrize("cell", list(CELLS))
def test_both_kernels_compile_for_a_v5e_at_the_cells_size(cell, one_chip, monkeypatch):
    """Mosaic refuses here what it would refuse on the chip (a block off
    the tiling, too much vector memory); nothing runs."""
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    d, f, g = CELLS[cell]
    for form, k, n, dtype in _products(d, f):
        text = _lowered(form, k, n, dtype, g, one_chip).lower().compile().as_text()
        assert f"grouped_matmul_{form}" in text and "tpu_custom_call" in text, (form, k, n)
        assert "ragged-dot" not in text


def test_the_kernels_bodies_hold_no_source_path(monkeypatch):
    """A Mosaic call's serialized body is part of the program's cache key:
    with a file's path in it two checkouts of the same code are two
    programs (PERF.md section 7)."""
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    d, f, g = CELLS["16k"]
    for form, k, n, dtype in _products(d, f)[2:4]:
        text = _lowered(form, k, n, dtype, g, None).lower(lowering_platforms=("tpu",)).as_text()
        bodies = [base64.b64decode(b) for b in re.findall(r"body\\22: \\22([A-Za-z0-9+/=]+)\\22", text)]
        assert len(bodies) == 1
        assert b".py" not in bodies[0] and b"grouped_matmul" not in bodies[0].replace(b"grouped_matmul_", b"")
