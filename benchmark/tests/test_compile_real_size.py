"""Each cell's step program compiled at its real size for a described
``v5e:2x2`` (nothing runs, no chip needed): the chip's compiler takes it,
and it needs the bytes the cells were sized from, within a tenth.

The topology is described inside a fixture, never at import: only one
process may hold the TPU's library.
"""

import os

import pytest

from benchmark import run

os.environ.setdefault("TPU_LOG_DIR", "disabled")

GB = 1e9
# Sized from the code (ISSUE 24): bytes of the step program on a device.
WANT_GB = {
    "hop-h1024.online-steady": 7.03,
    "hop-h1024.batch-job": 7.03,
    "hop-h1024.online-dp4": 7.03,
    "gat-c2.batch-job": 7.07,
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps the description away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _step_bytes(workload, topo) -> float:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dragonfly2_tpu.models.gnn import NeighborTable
    from dragonfly2_tpu.trainer.train import (
        TrainConfig, TrainState, _graph_train_step, _make_optimizer,
    )

    cell, config = run.load_cell_files(workload)
    module = run.load_module("configs", cell["config"])
    g, m = config["graph"], config["model"]
    n, k, batch = g["num_nodes"], g["max_neighbors"], cell["driver_params"]["batch_size"]
    feat = g["node_feature_dim"]
    if "hops" in m:
        from dragonfly2_tpu.models.hop import HopRanker as Model

        feat = feat * (1 + 2 * m["hops"]) + 2
    else:
        from dragonfly2_tpu.models.gnn import GATRanker as Model
    model = Model(module.model_config(m))

    mesh = Mesh(list(topo.devices)[: cell["chips"]], ("data",))
    repl, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    spec = lambda shape, dtype, s=repl: jax.ShapeDtypeStruct(shape, dtype, sharding=s)
    nf = spec((n, feat), jnp.float32)
    table = NeighborTable(spec((n, k), jnp.int32), spec((n, k), jnp.float32),
                          spec((n, k, 1), jnp.float32))
    ids = spec((batch,), jnp.int32, rows)

    def make_state():
        z = lambda s: jnp.zeros(s.shape, s.dtype)
        params = model.init(
            jax.random.PRNGKey(0), z(nf), jax.tree_util.tree_map(z, table),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        )["params"]
        return TrainState.create(
            apply_fn=model.apply, params=params, tx=_make_optimizer(TrainConfig(), 100),
            dropout_rng=jax.random.PRNGKey(1),
        )

    state = jax.tree_util.tree_map(
        lambda s: spec(s.shape, s.dtype), jax.eval_shape(make_state)
    )
    step = jax.jit(
        lambda s, f, t, a, b, y: _graph_train_step(s, f, t, a, b, y, None),
        donate_argnums=(0,),
    )
    mem = step.lower(state, nf, table, ids, ids, spec((batch,), jnp.float32, rows)).compile().memory_analysis()
    return (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    ) / GB


@pytest.mark.parametrize("workload", sorted(WANT_GB))
def test_step_program_compiles_for_the_chip_at_the_cells_size(workload, topo):
    got = _step_bytes(workload, topo)
    assert got == pytest.approx(WANT_GB[workload], rel=0.1), f"{got:.2f} GB"
    assert got < 16.0
