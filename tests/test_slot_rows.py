"""``ops/slot_rows.py``: the two row-moving kernels in interpret mode
against ``jnp.take`` and ``.at[].add``, over the shapes of index plan the
stream ranker's expert layer gives them (``models/stream.py::_block_plan``);
and, compiled for a described v5e, at the stream cell's real size.  Values
and bits, never a time.

The topology is described inside a fixture, never at import (only one
process may load the TPU's library at a time)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dragonfly2_tpu.models import stream
from dragonfly2_tpu.ops import slot_rows

T = 256          # tokens = rows of a block


def _plan(kind: str):
    """(rows, valid) of one block of T sorted slots, made by the layer's
    own ``_block_plan`` from the slots each held expert received."""
    rng = np.random.default_rng(5)
    groups = {
        # no slot is held: every row is padding and names itself
        "no-valid-slot": ([[], [], [], []], 0),
        # 100 held slots, then padding rows 100..255: tokens above 100 are
        # named by a held slot and by a padding row
        "part-filled": ([rng.choice(T, n, replace=False) for n in (40, 0, 35, 25)], 0),
        # held slots over the six tenths the fixed blocks cover: a block
        # that only the held share makes the loop run, half of it padding
        "past-the-blocks": ([rng.choice(T, n, replace=False) for n in (250, 200, 256, 190)], 3),
        # token 17 ends expert 0's group and starts expert 1's: the same
        # row twice in a row; token 255 then 0 across the next edge
        "adjacent-repeats": ([[3, 9, 17], [17, 40, 255], [0, 5], [5]], 0),
        # a full block inside the held slots: group edges and no padding
        "full-of-held": ([rng.choice(T, n, replace=False) for n in (250, 200, 256, 190)], 1),
    }[kind]
    tokens, block = groups
    tokens = [np.sort(np.asarray(g, np.int32)) for g in tokens]
    sizes = jnp.asarray([len(g) for g in tokens], jnp.int32)
    slots = np.concatenate([*tokens, np.zeros(4 * T, np.int32)])[: 4 * T].astype(np.int32)
    rows, _, per, valid = stream._block_plan(
        jnp.int32(block), T, jnp.cumsum(sizes), jnp.asarray(slots), jnp.ones((4 * T,), jnp.float32)
    )
    assert int(per.sum()) == T
    return rows, valid


KINDS = ["no-valid-slot", "part-filled", "past-the-blocks", "adjacent-repeats", "full-of-held"]


def test_the_plans_are_what_their_names_say():
    rows, valid = _plan("no-valid-slot")
    assert not bool(valid.any()) and rows.tolist() == list(range(T))
    rows, valid = _plan("part-filled")
    held = set(np.asarray(rows)[np.asarray(valid)].tolist())
    assert int(valid.sum()) == 100 and held & set(np.asarray(rows)[~np.asarray(valid)].tolist())
    rows, valid = _plan("past-the-blocks")
    assert 0 < int(valid.sum()) < T            # block 3 of 896 held slots: 128 left
    rows, valid = _plan("adjacent-repeats")
    assert rows[:9].tolist() == [3, 9, 17, 17, 40, 255, 0, 5, 5]
    rows, valid = _plan("full-of-held")
    assert bool(valid.all()) and int((rows[1:] <= rows[:-1]).sum()) >= 1


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
@pytest.mark.parametrize("kind", KINDS)
def test_gather_rows_is_take_bit_for_bit(kind, dtype):
    rows, _ = _plan(kind)
    d = 256 if dtype == jnp.bfloat16 else 128
    x = jnp.asarray(np.random.default_rng(1).normal(size=(T, d)), dtype)
    got = slot_rows.gather_rows(x, rows, chunk=64)
    assert got.dtype == x.dtype and got.shape == (T, d)
    assert bool((got == jnp.take(x, rows, axis=0)).all())


@pytest.mark.parametrize("width,chunk", [(128, 32), (256, 64), (128, 256)],
                         ids=["128-wide-chunks-of-32", "256-wide-chunks-of-64", "one-chunk"])
@pytest.mark.parametrize("kind", KINDS)
def test_add_rows_is_the_sequential_sum_on_every_call(kind, width, chunk):
    """Each row gets its addends in slot order, one float32 addition each:
    XLA's ``.at[].add`` on the CPU does the same, so the bits are equal;
    and twice the same call gives the same bits."""
    rows, valid = _plan(kind)
    rng = np.random.default_rng(2)
    y = jnp.asarray(rng.normal(size=(T, width)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(T, width)), jnp.float32)
    u = jnp.where(valid[:, None], u, 0.0) if kind == "no-valid-slot" else u
    got = slot_rows.add_rows(y, rows, u, chunk=chunk)
    want = np.asarray(y).copy()
    for r, row in zip(np.asarray(rows), np.asarray(u)):
        want[r] += row
    assert bool((np.asarray(got) == want).all())
    assert bool((got == y.at[rows].add(u)).all())
    assert bool((slot_rows.add_rows(y, rows, u, chunk=chunk) == got).all())


@pytest.mark.parametrize("dtype,width", [(jnp.bfloat16, 256), (jnp.float32, 128), (jnp.bfloat16, 512)],
                         ids=["bfloat16", "float32", "bfloat16-wider"])
def test_pack_makes_rows_of_words_and_the_gather_undoes_it(dtype, width):
    x = jnp.asarray(np.random.default_rng(3).normal(size=(24, width)), dtype)
    packed = slot_rows.pack(x)
    assert packed.shape == (24, 1, width * x.dtype.itemsize // 4) and packed.dtype.itemsize == 4
    if dtype == jnp.bfloat16:       # column c in the low half, c + D/2 in the high
        bits = np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint16)).astype(np.uint32)
        assert (np.asarray(packed)[:, 0] == (bits[:, : width // 2] | bits[:, width // 2 :] << 16)).all()
    else:
        assert bool((slot_rows.unpack(packed) == x).all())
    every = jnp.arange(24, dtype=jnp.int32)
    assert bool((slot_rows.gather_packed(packed, every, dtype) == x).all())
    assert slot_rows.pack(x, slot_rows.XLA) is x and slot_rows.unpack(x, slot_rows.XLA) is x


@pytest.mark.parametrize("width,dtype,on_tpu", [
    (2048, jnp.bfloat16, slot_rows.KERNEL), (128, jnp.float32, slot_rows.KERNEL),
    (128, jnp.bfloat16, slot_rows.XLA), (32, jnp.float32, slot_rows.XLA), (2048, jnp.float16, slot_rows.XLA),
], ids=["cell", "a-lane-group-of-float32", "half-a-lane-group", "tier-1s-width", "rows-no-kernel-packs"])
def test_the_carrier_is_read_from_the_backend_and_the_row(width, dtype, on_tpu, monkeypatch):
    assert slot_rows.row_mover(width, dtype) == slot_rows.XLA      # tier-1 runs on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert slot_rows.row_mover(width, dtype) == on_tpu


def test_the_xla_carrier_is_todays_operations():
    rows, _ = _plan("part-filled")
    x = jnp.asarray(np.random.default_rng(4).normal(size=(T, 32)), jnp.float32)
    text = str(jax.make_jaxpr(
        lambda x, r: slot_rows.add_packed(x, r, slot_rows.gather_packed(x, r, x.dtype, slot_rows.XLA), slot_rows.XLA)
    )(x, rows))
    assert "pallas_call" not in text and "gather" in text and "scatter-add" in text


def test_rows_that_are_not_whole_chunks_are_refused():
    y = jnp.zeros((8, 128), jnp.float32)
    with pytest.raises(TypeError, match="float16"):
        slot_rows.pack(y.astype(jnp.float16))
    with pytest.raises(ValueError, match="whole chunks"):
        slot_rows.add_rows(y, jnp.arange(48, dtype=jnp.int32) % 8, jnp.zeros((48, 128)), chunk=32)


# -- compiled for the chip at the stream cell's size --------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu from describing a chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("call", ["gather", "add"])
def test_the_kernels_compile_for_a_v5e_at_the_cells_size(call, one_chip, monkeypatch):
    """Mosaic refuses here what it would refuse on the chip (a slice off
    the tiling, too much scalar or vector memory); nothing runs."""
    monkeypatch.setattr(slot_rows, "_interpret", lambda: False)
    t, d = 32768, 2048
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    rows = sds((t,), jnp.int32)
    if call == "gather":
        text = jax.jit(slot_rows.gather_rows).lower(sds((t, d), jnp.bfloat16), rows).compile().as_text()
    else:
        text = jax.jit(slot_rows.add_rows, donate_argnums=0).lower(
            sds((t, d), jnp.float32), rows, sds((t, d), jnp.float32)
        ).compile().as_text()
    assert f"slot_rows_{call}" in text and "tpu_custom_call" in text
