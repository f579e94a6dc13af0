"""The stream cell's train dispatch compiled at its real size for a
described ``v5e:2x2`` (nothing runs, no chip needed): the chip's compiler
takes the chunked scan, the blockwise attention and the grouped product
at the published widths, and the program needs the bytes the cell was
sized from.  A minute and a half of compiling.

The topology is described inside a fixture, never at import: only one
process may hold the TPU's library (``test_compile_real_size.py`` holds
the other cells' compiles; run the two files in one process or apart, not
under several workers).
"""

import os

import pytest

from benchmark import run

os.environ.setdefault("TPU_LOG_DIR", "disabled")

GB = 1e9
WORKLOAD = "qwen3-next-80b-a3b-t16.stream-packed-4k"
# By the compiler's count for the described chip (PR 27): parameters and
# both moments 7.52 GB, the step's temporaries 6.70 GB of which 2.50 GB
# are the gradients, together 14.21 GB of the chip's 16.9.
WANT_GB = 14.21


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps the description away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_dispatch_compiles_for_the_chip_at_the_cells_size(topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from dragonfly2_tpu.models import build_ranker
    from dragonfly2_tpu.models.gnn import NeighborTable
    from dragonfly2_tpu.trainer.train import (
        TrainConfig, TrainState, _graph_train_step, _make_optimizer,
    )

    cell, config = run.load_cell_files(WORKLOAD)
    g, m, p = config["graph"], config["model"], cell["driver_params"]
    m["positions"] = p["batch_size"] // p["rows"]
    ranker = build_ranker(run.load_module("configs", cell["config"]).model_config(m))
    one = SingleDeviceSharding(topo.devices[0])
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
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
    params = sum(s.size for s in jax.tree_util.tree_leaves(state.params))
    assert params == pytest.approx(626e6, rel=0.005)          # 10.0 GB at 16 B a parameter

    def dispatch(state, hop, table, es, ed, y):       # OnlineGraphTrainer._train_dispatch
        def body(carry, xs):
            a, b, t = xs
            return _graph_train_step(carry, hop, table, a, b, t, ranker.query_feats(b, t))
        return jax.lax.scan(body, state, (es, ed, y))

    shape = (p["super_steps"], p["batch_size"])
    compiled = jax.jit(dispatch, donate_argnums=(0,)).lower(
        state, nf, table, spec(shape, jnp.int32), spec(shape, jnp.int32), spec(shape, jnp.float32)
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ragged-dot" in text     # the grouped product is the chip's own
    mem = compiled.memory_analysis()
    got = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    ) / GB
    assert got == pytest.approx(WANT_GB, rel=0.1), f"{got:.2f} GB"
    assert got < 16.9
