"""The plain references against the flax modules, at a tiny size on the
CPU in float32: the snapshot tables, the weights each makes from the seed,
the dropout masks and the forward pass agree to rounding, so on the chip
what is left between the program and the reference is precision."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run, traffic

N, K, B = 300, 4, 64


@pytest.fixture(scope="module")
def inputs():
    return traffic.make_inputs(
        dict(num_nodes=N, probe_degree=6, records_per_block=B, pool_blocks=1), 5
    )


@pytest.fixture(scope="module")
def table(inputs):
    from dragonfly2_tpu.models.gnn import build_neighbor_table

    return build_neighbor_table(N, *inputs.topo, max_neighbors=K)


def _same_tree(flax_params, ref_params, common):
    a = common.flatten(jax.tree_util.tree_map(np.asarray, dict(flax_params)))
    b = common.flatten(ref_params)
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], np.asarray(b[k]), err_msg=k)


def test_neighbor_table_and_hop_features_match_the_programs(inputs, table):
    from dragonfly2_tpu.models.hop import precompute_hop_features

    C = run.load_module("reference", "hop-h1024").C
    idx, msk, ef = C.neighbor_table(N, *inputs.topo, K)
    assert msk.sum(1).max() == K and (msk.sum(1) == K).mean() > 0.5   # the sampler is exercised
    np.testing.assert_array_equal(idx, table.indices)
    np.testing.assert_array_equal(msk, table.mask)
    np.testing.assert_array_equal(ef, table.edge_feats)
    want = precompute_hop_features(jnp.asarray(inputs.node_feats), table, hops=2)
    np.testing.assert_allclose(
        C.hop_features(inputs.node_feats, idx, msk, ef, 2), np.asarray(want), rtol=1e-6, atol=1e-6
    )


def test_hop_reference_matches_flax_with_dropout_on(inputs, table):
    from dragonfly2_tpu.models.hop import HopConfig, HopRanker, precompute_hop_features

    R = run.load_module("reference", "hop-h1024")
    m = dict(hidden=32, out_dim=64, hops=2, node_embed_dim=32, dropout=0.1)
    model = HopRanker(HopConfig(hidden=32, dtype=jnp.float32))
    hop = precompute_hop_features(jnp.asarray(inputs.node_feats), table, hops=2)
    src, dst, _ = inputs.blocks[0]
    key, drop = jax.random.PRNGKey(7), jax.random.PRNGKey(9)
    params = model.init(key, hop, table, jnp.asarray(src[:2]), jnp.asarray(src[:2]))["params"]
    mine = R.init_params(key, N, hop.shape[1], m)
    _same_tree(params, mine, R.C)
    with jax.default_matmul_precision("highest"):
        want = model.apply(
            {"params": params}, hop, table, jnp.asarray(src), jnp.asarray(dst),
            train=True, rngs={"dropout": drop},
        )
        masks = [R.C.keep_mask(drop, (R.ENC, "Dropout_0", c), (B, 32), 0.1) for c in (1, 2)]
        got = R.predict(mine, hop, src, dst, *masks, 0.1, R.C.KEEP_F32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_gat_reference_matches_flax_with_dropout_on(inputs, table):
    from dragonfly2_tpu.models.gnn import GATRanker, GNNConfig

    R = run.load_module("reference", "gat-c2")
    m = dict(hidden=128, out_dim=64, num_layers=2, num_heads=4, edge_dim=1,
             node_embed_dim=32, dropout=0.1)
    model = GATRanker(GNNConfig(dtype=jnp.float32))
    nf = jnp.asarray(inputs.node_feats)
    src, dst, _ = inputs.blocks[0]
    key, drop = jax.random.PRNGKey(7), jax.random.PRNGKey(9)
    params = model.init(key, nf, table, jnp.asarray(src[:2]), jnp.asarray(src[:2]))["params"]
    mine = R.init_params(key, N, nf.shape[1], m)
    _same_tree(params, mine, R.C)
    with jax.default_matmul_precision("highest"):
        want = model.apply(
            {"params": params}, nf, table, jnp.asarray(src), jnp.asarray(dst),
            train=True, rngs={"dropout": drop},
        )
        masks = [R.C.keep_mask(drop, (f"Dropout_{i}", 1), (N, 128), 0.1) for i in range(2)]
        ref_table = tuple(jnp.asarray(a) for a in R.C.neighbor_table(N, *inputs.topo, K))
        got = R.predict(mine, nf, ref_table, src, dst, masks, m, R.C.KEEP_F32, block=100)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_reference_optimizer_follows_optax(inputs):
    """Three steps of the written-out AdamW against the program's own
    optimizer on the same gradients."""
    from dragonfly2_tpu.trainer.train import TrainConfig, _make_optimizer

    C = run.load_module("reference", "hop-h1024").C
    rng = np.random.default_rng(0)
    params = {"a": {"kernel": jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)},
              "b": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}
    grads = [jax.tree_util.tree_map(lambda p: jnp.asarray(rng.normal(size=p.shape) * s, jnp.float32), params)
             for s in (3.0, 0.01, 1.0)]
    tx = _make_optimizer(TrainConfig(), 1000)
    state, theirs = tx.init(params), params
    import optax

    for g in grads:
        updates, state = tx.update(g, state, theirs)
        theirs = optax.apply_updates(theirs, updates)
    train = {"learning_rate": 3e-4, "weight_decay": 1e-4, "warmup_steps": 100}
    got = C.follow(params, lambda p, t: (jnp.float32(0), grads[t]), 3, train)
    want_change = {k: float(jnp.linalg.norm(v)) for k, v in C.flatten(
        jax.tree_util.tree_map(lambda a, b: a - b, theirs, params)).items()}
    for k, v in want_change.items():
        assert got["change_norm"][k] == pytest.approx(v, rel=1e-4)
