"""``ops/attention_bwd.py``: attention's backward kernel in interpret mode
against the loop it replaces (``_bwd_xla``, the oracle) and, through
``segment_attention``'s VJP, against a dense masked softmax; its plan of
visits against ``attention_pairs``' count; which carrier runs where, and
what the ``trainer/run`` span says of it; and, compiled for a described
v5e, the kernel at the three stream cells' shapes and its call under
``stream/attn/core``.  Values and counts, never a time.

The topology is described inside a fixture, never at import (only one
process may load the TPU's library at a time)."""

import base64
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark import run as bench
from dragonfly2_tpu.models import stream
from dragonfly2_tpu.ops import attention_bwd as ab
from tests import _glm_sizes, _smallthinker_sizes, _stream_sizes
from tests.test_window_attention import SCALE, _dense

F32, BF16 = jnp.float32, jnp.bfloat16

# Segment lengths of a row: one segment; several, one shorter than a block
# and one crossing blocks.
SEGMENTS = {"one": lambda l: [l], "packed": lambda l: [3, l // 2 - 3, 1, l // 4, l - l // 2 - l // 4 - 1]}
# (query heads a key head, head width): the GLM cell's latent heads, the
# 16k cell's and the 4k cell's groups.
GROUPS = [(1, 256), (7, 128), (8, 256)]
BLK = 16


def _seg(rows, l):
    dst = np.stack([np.repeat(np.arange(len(SEGMENTS[r](l))), SEGMENTS[r](l)) for r in rows]).astype(np.int32)
    return stream.segments(jnp.asarray(dst.reshape(-1)), l)[1]


def _operands(r, k, g, l, d, dtype, seed):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32), dtype)
    return f(r, k, g, l, d), f(r, k, l, d), f(r, k, l, d), f(r, k, g, l, d)


def _both(q, k, v, seg, do, blk, window):
    o, lse = stream._attention_fwd(q, k, v, seg, blk, SCALE, window)
    first = stream._first_key_blocks(seg, blk, window)
    run = lambda c: ab.backward(q, k, v, seg, o, lse, do, first, blk, SCALE, window, c)
    return run(ab.KERNEL), run(ab.XLA)


@pytest.mark.parametrize("l", [BLK, 4 * BLK], ids=["one-block", "four-blocks"])
@pytest.mark.parametrize("window", [0, 20], ids=["full", "window-edge-in-a-block"])
@pytest.mark.parametrize("rows", [("one",), ("packed", "one")], ids="+".join)
@pytest.mark.parametrize("grp,d", GROUPS, ids=lambda x: str(x))
def test_the_kernel_is_the_loop(grp, d, rows, window, l):
    """bfloat16 operands, as the chip runs them: ``dk`` and ``dv`` the
    loop's to the last bit or to one rounding of the float32 sum, ``dq``
    likewise; the gradients in their operands' dtypes."""
    q, k, v, do = _operands(len(rows), 2 if grp == 1 else 1, grp, l, d, BF16, seed=grp + d + l + window)
    got, want = _both(q, k, v, _seg(rows, l), do, BLK, window)
    for a, b, like in zip(got, want, (q, k, v)):
        assert a.shape == b.shape == like.shape and a.dtype == b.dtype == like.dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=0, atol=2.0 ** -7 * float(np.abs(b).max()))


@pytest.mark.parametrize("window", [0, 5, 20], ids=["full", "window-5", "window-20"])
def test_the_kernel_is_the_loop_in_float32(window):
    """Operands in float32, three rows of uneven segments: the same
    products in another order of sums."""
    q, k, v, do = _operands(3, 2, 3, 64, 8, F32, seed=window)
    seg = _seg(("packed", "one", "packed"), 64)
    got, want = _both(q, k, v, seg, do, 8, window)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("window,block", [(0, 8), (12, 8), (20, 16), (64, 16)])
def test_segment_attention_with_the_kernel_is_the_dense_softmax(window, block, monkeypatch):
    """Through ``segment_attention``'s VJP, the kernel carrier forced (it
    runs in interpret mode here): all three gradients the dense masked
    softmax's."""
    monkeypatch.setattr(ab, "carrier", lambda *a: ab.KERNEL)
    q, k, v, w = _operands(2, 2, 3, 64, 8, F32, seed=block + window)
    seg = _seg(("packed", "one"), 64)
    with jax.default_matmul_precision("highest"):
        ours = jax.grad(
            lambda q, k, v: (stream.segment_attention(q, k, v, seg, block, SCALE, window) * w).sum(), (0, 1, 2)
        )(q, k, v)
        theirs = jax.grad(lambda q, k, v: (_dense(q, k, v, seg, window) * w).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(ours, theirs):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)
    text = str(jax.make_jaxpr(jax.grad(lambda q: stream.segment_attention(q, k, v, seg, block, SCALE, window).sum()))(q))
    assert "pallas_call" in text


# -- the plan ---------------------------------------------------------------------------------

PLANS = [(("one",), 0), (("one",), 20), (("packed",), 0), (("packed",), 20), (("packed", "one", "packed"), 0),
         (("one", "packed"), 36)]


@pytest.mark.parametrize("rows,window", PLANS)
def test_the_plan_visits_the_pairs_attention_pairs_counts(rows, window):
    """Each row's pairs are ``first[r, i] <= j <= i``, query block by query
    block with the key blocks ascending, once each: as many as
    ``attention_pairs`` counts; the grid's length is the band's by
    position, and the visits past a row's count repeat its last, the
    diagonal of the last query block."""
    l = 8 * BLK
    seg = _seg(rows, l)
    first = stream._first_key_blocks(seg, BLK, window)
    visits = ab.band_visits(l, BLK, window)
    assert visits == int(stream.attention_pairs(seg[:1] * 0, BLK, window)[1])
    qb, kb, real = (np.asarray(a) for a in ab.plan(first, visits))
    assert int(real.sum()) == int(stream.attention_pairs(seg, BLK, window)[0])
    first = np.asarray(first)
    for r in range(len(rows)):
        i, j, n = qb[r * visits:(r + 1) * visits], kb[r * visits:(r + 1) * visits], int(real[r])
        want = [(a, b) for a in range(l // BLK) for b in range(first[r, a], a + 1)]
        assert list(zip(i[:n].tolist(), j[:n].tolist())) == want
        assert (i[n:] == l // BLK - 1).all() and (j[n:] == l // BLK - 1).all()


def test_a_row_past_every_segment_start_runs_the_diagonal_alone():
    seg = jnp.arange(64, dtype=jnp.int32)[None]            # every record its own segment
    first = stream._first_key_blocks(seg, 8, 0)
    _, _, real = ab.plan(first, ab.band_visits(64, 8, 0))
    assert int(real[0]) == 8 == int(stream.attention_pairs(seg, 8, 0)[0])


# -- the carrier ----------------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,d,grp,block,positions,on_tpu", [
    (BF16, 256, 1, 512, 4096, ab.KERNEL), (BF16, 128, 7, 512, 16384, ab.KERNEL), (BF16, 256, 8, 512, 4096, ab.KERNEL),
    (F32, 8, 2, 8, 32, ab.XLA), (BF16, 64, 1, 512, 4096, ab.XLA), (F32, 256, 1, 512, 4096, ab.XLA),
    (BF16, 256, 1, 64, 4096, ab.XLA), (BF16, 256, 8, 512, 131072, ab.XLA),
], ids=["glm", "16k", "4k", "tier-1s-widths", "head-64", "float32", "block-64", "row-too-long-for-vmem"])
def test_the_carrier_is_read_from_the_backend_and_the_shapes(dtype, d, grp, block, positions, on_tpu, monkeypatch):
    assert ab.carrier(dtype, d, grp, block, positions) == ab.XLA        # tier-1 runs on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ab.carrier(dtype, d, grp, block, positions) == on_tpu


CELLS = {
    "glm": "glm-4-7-flash-t8.stream-packed-4k",
    "16k": "smallthinker-21b-a3b-t4.stream-packed-16k",
    "4k": "qwen3-next-80b-a3b-t16.stream-packed-4k",
}


def _cell_config(workload):
    cell, config = bench.load_cell_files(workload)
    m, p = config["model"], cell["driver_params"]
    m["positions"] = p["batch_size"] // p["rows"]
    return bench.load_module("configs", cell["config"]).model_config(m)


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_span_names_the_attention_carrier_of_each_cell(cell, monkeypatch):
    cfg = _cell_config(CELLS[cell])
    assert stream.carrier_attrs(cfg)["attn_carrier"] == ab.XLA
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert stream.carrier_attrs(cfg)["attn_carrier"] == ab.KERNEL


@pytest.mark.parametrize("sizes", [_stream_sizes, _smallthinker_sizes, _glm_sizes], ids=lambda s: s.NAME)
def test_the_span_names_it_where_a_layer_is_attention_and_nowhere_else(sizes):
    cfg = bench.load_module("configs", sizes.NAME).model_config(sizes.M)
    assert stream.carrier_attrs(cfg)["attn_carrier"] == ab.XLA
    none = dataclasses.replace(cfg, layers=(stream.Mixer(stream.DELTANET),) * cfg.num_hidden_layers)
    assert "attn_carrier" not in stream.carrier_attrs(none)


# -- compiled for a described v5e -----------------------------------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu from describing a chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# (key heads, group, positions, head width, window) of one row of each cell
SHAPES = {"glm": (20, 1, 4096, 256, 0), "16k-window": (4, 7, 16384, 128, 4096), "4k": (2, 8, 4096, 256, 0)}


def _traced(shape, sharding):
    hk, grp, l, d, window = shape
    sds = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=sharding)
    fn = lambda q, k, v, seg, o, lse, do, first: ab.backward(q, k, v, seg, o, lse, do, first, 512, d ** -0.5, window)
    return jax.jit(fn).trace(
        sds((1, hk, grp, l, d), BF16), sds((1, hk, l, d), BF16), sds((1, hk, l, d), BF16), sds((1, l), jnp.int32),
        sds((1, hk, grp, l, d), F32), sds((1, hk, grp, l), F32), sds((1, hk, grp, l, d), BF16),
        sds((1, l // 512), jnp.int32),
    )


@pytest.mark.parametrize("cell", list(SHAPES))
def test_the_kernel_compiles_for_a_v5e_at_the_cells_shapes(cell, one_chip, monkeypatch):
    """Mosaic refuses here what it would refuse on the chip (a block off
    the tiling, more vector memory than the core has); nothing runs."""
    monkeypatch.setattr(ab, "_interpret", lambda: False)
    text = _traced(SHAPES[cell], one_chip).lower().compile().as_text()
    assert "attention_bwd" in text and "tpu_custom_call" in text


def test_the_kernels_body_holds_no_source_path(monkeypatch):
    """A Mosaic call's serialized body is part of the program's cache key:
    with a file's path in it two checkouts of the same code are two
    programs (PERF.md section 7)."""
    monkeypatch.setattr(ab, "_interpret", lambda: False)
    text = _traced(SHAPES["glm"], None).lower(lowering_platforms=("tpu",)).as_text()
    bodies = [base64.b64decode(b) for b in re.findall(r"body\\22: \\22([A-Za-z0-9+/=]+)\\22", text)]
    assert len(bodies) == 1
    assert b".py" not in bodies[0]


def test_the_kernels_call_is_named_by_the_cores_scope(one_chip, monkeypatch):
    """A latent attention layer's row, recomputed in its backward as
    ``_row_by_row`` runs it, compiled for a v5e: the kernel's one call
    carries ``stream/attn/core`` in its ``op_name``, which
    ``attn_core_share`` and ``attn_core_roofline`` read."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    base = bench.load_module("configs", _glm_sizes.NAME).model_config(_glm_sizes.M)
    cfg = dataclasses.replace(
        base, hidden_size=256, num_attention_heads=2, q_lora_rank=128, kv_lora_rank=128, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, positions=1024, attn_block=512, dtype=BF16,
    )
    kind = stream.layer_kinds(cfg)[0]
    shapes = {name: shape for name, (_, shape) in stream.parameter_shapes(cfg, 10, 16).items()
              if name.startswith("layer_0.attn.")}
    p = {name.split(".")[-1]: jax.ShapeDtypeStruct(shape, F32, sharding=one_chip) for name, shape in shapes.items()}
    x = jax.ShapeDtypeStruct((1, 1024, 256), BF16, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((1, 1024), jnp.int32, sharding=one_chip)

    def loss(p, x, seg):
        y = stream._row_by_row(lambda p, x, seg: stream.latent_attention(p, x, seg, cfg, kind), p, x, seg)
        return y.astype(F32).sum()

    text = jax.jit(jax.grad(loss, (0, 1))).lower(p, x, seg).compile().as_text()
    calls = [line for line in text.split("\n") if " custom-call(" in line and "attention_bwd" in line]
    assert len(calls) == 1, calls
    assert "/stream/attn/core/" in re.search(r'op_name="([^"]*)"', calls[0]).group(1)
    # No pair's ``dk``, ``dv`` added into a float32 array of the row.
    assert not re.search(r"= f32\[1,2,1024,256\][^ ]* dynamic-update-slice", text)
