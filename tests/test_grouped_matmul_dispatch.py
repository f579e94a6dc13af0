"""The two stream cells' train dispatches compiled at their real sizes for a
described v5e on the grouped products' kernel carrier (``jax`` told it
runs on a TPU, so that every carrier picks what the chip's would:
``ops/grouped_matmul.py``, ``ops/slot_rows.py``, ``ops/delta_scan.py``):
no ``ragged-dot`` custom call is left, every grouped kernel's call is
named by ``stream/moe/experts/grouped`` (what ``moe_products_share``
sums), and ``program_scopes.restore``, with no product to restore, names
the rest as on the parent and changes nothing but ``op_name``s.  Nothing
runs; about a minute of compiling a cell.

The topology is described inside a fixture, never at import (only one
process may load the TPU's library at a time)."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark import run as bench
from dragonfly2_tpu.models import build_ranker, stream
from dragonfly2_tpu.ops import grouped_matmul
from dragonfly2_tpu.trainer import program_scopes

CELLS = {
    "16k": "smallthinker-21b-a3b-t4.stream-packed-16k",
    "4k": "qwen3-next-80b-a3b-t16.stream-packed-4k",
}
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu from describing a chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _lowered_dispatch(workload, sharding):
    """``OnlineGraphTrainer._train_dispatch`` of the cell, lowered from
    shapes alone (no state is made)."""
    from dragonfly2_tpu.models.gnn import NeighborTable
    from dragonfly2_tpu.trainer.train import TrainConfig, TrainState, _graph_train_step, _make_optimizer

    cell, config = bench.load_cell_files(workload)
    g, m, p = config["graph"], config["model"], cell["driver_params"]
    m["positions"] = p["batch_size"] // p["rows"]
    cfg = bench.load_module("configs", cell["config"]).model_config(m)
    ranker = build_ranker(cfg)
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    n, k = g["num_nodes"], g["max_neighbors"]
    nf = spec((n, g["node_feature_dim"] * (1 + 2 * m["hops"]) + 2), jnp.float32)
    table = NeighborTable(spec((n, k), jnp.int32), spec((n, k), jnp.float32), spec((n, k, 1), jnp.float32))

    def make_state():
        z = lambda s: jnp.zeros(s.shape, s.dtype)
        ids = jnp.zeros((2,), jnp.int32)
        v = ranker.module.init(jax.random.PRNGKey(0), z(nf), jax.tree_util.tree_map(z, table), ids, ids)
        return TrainState.create(
            apply_fn=ranker.module.apply, params=v["params"], tx=_make_optimizer(TrainConfig(), 1000),
            dropout_rng=jax.random.PRNGKey(1), aux=v.get("aux"),
        )

    state = jax.tree_util.tree_map(lambda s: spec(s.shape, s.dtype), jax.eval_shape(make_state))

    def dispatch(state, hop, table, es, ed, y):
        def body(carry, xs):
            a, b, t = xs
            return _graph_train_step(carry, hop, table, a, b, t, ranker.query_feats(b, t))
        return jax.lax.scan(body, state, (es, ed, y))

    shape = (p["super_steps"], p["batch_size"])
    lowered = jax.jit(dispatch, donate_argnums=(0,)).lower(
        state, nf, table, spec(shape, jnp.int32), spec(shape, jnp.int32), spec(shape, jnp.float32)
    )
    return cfg, lowered


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_dispatch_runs_the_grouped_kernels_under_their_scope(cell, one_chip, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, lowered = _lowered_dispatch(CELLS[cell], one_chip)
    assert stream.grouped_carrier(cfg) == grouped_matmul.KERNEL
    assert program_scopes.source_products(lowered) == []
    text = lowered.compile().as_text()
    assert "ragged-dot" not in text
    calls = [
        line for line in text.split("\n")
        if " custom-call(" in line and "grouped_matmul_" in line
    ]
    # Four layers, each a loop over blocks: three products forward, seven
    # backward, each one call in its loop's body.
    assert len(calls) == 4 * (3 + 7), len(calls)
    names = [_OP_NAME.search(line).group(1) for line in calls]
    assert all("/stream/moe/experts/grouped/" in name for name in names), names
    assert sorted("transpose(" in name for name in names) == [False] * 12 + [True] * 28
    restored = program_scopes.restore(text, [])
    strip = lambda t: re.sub(r', metadata=\{[^}]*\}', "", t)
    assert strip(restored) == strip(text)
    for line in restored.split("\n"):
        if " custom-call(" in line and "grouped_matmul_" in line:
            assert "/stream/moe/experts/grouped/" in _OP_NAME.search(line).group(1)
