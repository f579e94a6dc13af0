"""Aggregation ops: pallas kernel vs XLA oracle; shard_map aggregation vs
single-device; streaming trainer ingest + checkpoint/resume determinism."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from dragonfly2_tpu.models.gnn import build_neighbor_table
from dragonfly2_tpu.ops import (
    bucket_edges_by_block,
    masked_mean_aggregate,
    segment_mean,
    segment_sum,
    segment_sum_pallas,
)
from dragonfly2_tpu.parallel import create_mesh
from dragonfly2_tpu.parallel.graph_sharding import (
    make_sharded_table,
    pad_nodes_for_mesh,
    sharded_neighbor_aggregate,
)


class TestSegmentOps:
    def test_segment_sum_matches_numpy(self):
        rng = np.random.default_rng(0)
        e, d, n = 500, 16, 40
        vals = rng.normal(size=(e, d)).astype(np.float32)
        ids = rng.integers(0, n, e)
        got = np.asarray(segment_sum(jnp.asarray(vals), jnp.asarray(ids), n))
        want = np.zeros((n, d), np.float32)
        np.add.at(want, ids, vals)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_segment_mean(self):
        vals = jnp.ones((4, 2))
        ids = jnp.array([0, 0, 1, 3])
        got = np.asarray(segment_mean(vals, ids, 4))
        np.testing.assert_allclose(got[0], [1, 1])
        np.testing.assert_allclose(got[2], [0, 0])  # empty segment → 0


class TestBucketing:
    def test_bucketing_covers_all_edges(self):
        rng = np.random.default_rng(1)
        ids = rng.integers(0, 300, 1000)
        perm, dstl, w, block_node, is_first = bucket_edges_by_block(
            ids, 300, node_block=128, edge_block=128
        )
        assert w.sum() == 1000  # every real edge exactly once
        assert len(perm) % 128 == 0
        assert (dstl >= 0).all() and (dstl < 128).all()
        # every node block visited, first visit flagged once
        assert set(block_node) == {0, 1, 2}
        assert is_first.sum() == 3
        # real edges land in the right block
        real = w > 0
        global_dst = block_node.repeat(
            len(perm) // len(block_node)
        ) * 128 + dstl
        np.testing.assert_array_equal(np.sort(global_dst[real]), np.sort(ids))

    def test_empty_node_block_padded(self):
        # All edges hit node 0; blocks for nodes 128.. must still appear.
        ids = np.zeros(10, dtype=np.int64)
        perm, dstl, w, block_node, is_first = bucket_edges_by_block(
            ids, 256, node_block=128, edge_block=128
        )
        assert set(block_node) == {0, 1}
        assert is_first.sum() == 2


class TestPallasSegmentSum:
    def test_matches_oracle_interpret(self):
        rng = np.random.default_rng(2)
        e, d, n = 700, 128, 300
        vals = rng.normal(size=(e, d)).astype(np.float32)
        ids = rng.integers(0, n, e)
        want = np.asarray(segment_sum(jnp.asarray(vals), jnp.asarray(ids), n))
        # Exact path: f32-HIGHEST accumulate, tight tolerance.
        got = np.asarray(
            segment_sum_pallas(jnp.asarray(vals), ids, n, exact=True,
                               interpret=True)
        )
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        # Native MXU path (default): bf16 multiplicands, f32 accumulate.
        got16 = np.asarray(
            segment_sum_pallas(jnp.asarray(vals), ids, n, interpret=True)
        )
        np.testing.assert_allclose(got16, want, rtol=2e-2, atol=2e-2)

    def test_presorted_skips_permutation(self):
        from dragonfly2_tpu.ops.pallas_segment import bucket_edges_by_block

        rng = np.random.default_rng(5)
        e, d, n = 500, 64, 200
        vals = rng.normal(size=(e, d)).astype(np.float32)
        ids = rng.integers(0, n, e)
        perm, *_ = bucket_edges_by_block(ids, n, node_block=128, edge_block=128)
        pre = np.zeros((len(perm), d), np.float32)
        pre[: len(perm)] = vals[perm]
        got = np.asarray(
            segment_sum_pallas(jnp.asarray(pre), ids, n, presorted=True,
                               node_block=128, edge_block=128, exact=True,
                               interpret=True)
        )
        want = np.asarray(segment_sum(jnp.asarray(vals), jnp.asarray(ids), n))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_empty_segments_are_zero(self):
        vals = np.ones((4, 8), np.float32)
        ids = np.array([5, 5, 6, 200])
        got = np.asarray(
            segment_sum_pallas(jnp.asarray(vals), ids, 256, interpret=True)
        )
        assert got[5].sum() == 16.0
        assert got[0].sum() == 0.0
        assert got[130].sum() == 0.0

    def test_neighbor_gather_vjp_matches_take(self):
        import jax

        from dragonfly2_tpu.ops.pallas_segment import make_neighbor_gather

        rng = np.random.default_rng(7)
        n, k, d = 300, 8, 64
        idx = rng.integers(0, n, (n, k)).astype(np.int32)
        table = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        g = make_neighbor_gather(idx, n, edge_block=128, interpret=True)
        assert bool(jnp.array_equal(
            g(table), jnp.take(table, jnp.asarray(idx), axis=0)
        ))
        gc = jax.grad(lambda t: jnp.sum(jnp.sin(g(t)) * 0.01))(table)
        gr = jax.grad(
            lambda t: jnp.sum(jnp.sin(jnp.take(t, jnp.asarray(idx), axis=0)) * 0.01)
        )(table)
        rel = float(jnp.max(jnp.abs(gc - gr)) / jnp.max(jnp.abs(gr)))
        assert rel < 2e-2  # bf16 accumulate in the kernel backward

    def test_gather_fn_through_gatranker(self):
        """The GNNConfig(gather_fn=...) wiring end to end: same loss and
        gradients as the default path, and a mismatched table rejected."""
        import jax

        from dragonfly2_tpu.models import (
            GATRanker,
            GNNConfig,
            build_neighbor_table,
        )
        from dragonfly2_tpu.ops.pallas_segment import make_neighbor_gather

        rng = np.random.default_rng(11)
        n = 200
        src = rng.integers(0, n, 800)
        dst = rng.integers(0, n, 800)
        table = build_neighbor_table(n, src, dst, max_neighbors=8)
        nf = jnp.asarray(rng.normal(size=(n, 6)).astype(np.float32))
        es = jnp.asarray(rng.integers(0, n, 32).astype(np.int32))
        ed = jnp.asarray(rng.integers(0, n, 32).astype(np.int32))
        y = jnp.asarray(rng.normal(size=32).astype(np.float32))

        def loss_and_gradsum(cfg):
            model = GATRanker(cfg)
            params = model.init(
                jax.random.PRNGKey(0), nf, table, es[:2], ed[:2]
            )["params"]

            def loss(p):
                return jnp.mean(
                    (model.apply({"params": p}, nf, table, es, ed) - y) ** 2
                )

            l, g = jax.value_and_grad(loss)(params)
            return float(l), sum(
                float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(g)
            )

        base_cfg = GNNConfig(hidden=16, num_heads=2, node_embed_dim=4,
                             dropout=0.0)
        gf = make_neighbor_gather(
            np.asarray(table.indices), n, edge_block=128, interpret=True
        )
        l0, g0 = loss_and_gradsum(base_cfg)
        l1, g1 = loss_and_gradsum(
            GNNConfig(hidden=16, num_heads=2, node_embed_dim=4,
                      dropout=0.0, gather_fn=gf)
        )
        assert abs(l0 - l1) / max(abs(l0), 1e-6) < 1e-3
        assert abs(g0 - g1) / max(g0, 1e-6) < 5e-2
        # Wrong-snapshot gather_fn → loud error, not silent garbage.
        small = build_neighbor_table(50, src % 50, dst % 50, max_neighbors=4)
        bad = make_neighbor_gather(
            np.asarray(small.indices), 50, edge_block=128, interpret=True
        )
        model = GATRanker(GNNConfig(hidden=16, num_heads=2, node_embed_dim=4,
                                    dropout=0.0, gather_fn=bad))
        with pytest.raises((ValueError, TypeError)):
            model.init(jax.random.PRNGKey(0), nf, table, es[:2], ed[:2])

    def test_presorted_rejects_unbucketed_length(self):
        rng = np.random.default_rng(3)
        vals = rng.normal(size=(500, 32)).astype(np.float32)
        ids = rng.integers(0, 200, 500)
        with pytest.raises(ValueError):
            segment_sum_pallas(
                jnp.asarray(vals), ids, 200, presorted=True, interpret=True
            )


class TestShardedAggregation:
    def test_matches_single_device(self):
        mesh = create_mesh()
        rng = np.random.default_rng(3)
        n_raw, d, k = 100, 32, 8
        n = pad_nodes_for_mesh(n_raw, mesh)
        src = rng.integers(0, n_raw, 600)
        dst = rng.integers(0, n_raw, 600)
        feats = rng.normal(size=600).astype(np.float32)
        table = build_neighbor_table(n, src, dst, feats, max_neighbors=k)
        h = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))

        # Single-device oracle (same math inline).
        nbr = jnp.take(h, table.indices, axis=0)
        nbr = jnp.concatenate([nbr, table.edge_feats], axis=-1)
        m = table.mask[..., None]
        want = (nbr * m).sum(axis=1) / jnp.maximum(m.sum(axis=1), 1.0)

        from jax.sharding import NamedSharding, PartitionSpec as P

        h_sharded = jax.device_put(h, NamedSharding(mesh, P("data")))
        t_sharded = make_sharded_table(mesh, table)
        got = sharded_neighbor_aggregate(mesh, h_sharded, t_sharded)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


class TestStreamingTrainer:
    def _rows(self, cluster, n, seed):
        return cluster.generate_feature_rows(n, seed=seed)

    def test_stream_learns_and_checkpoints(self, tmp_path, cluster):
        from dragonfly2_tpu.trainer.streaming import StreamingConfig, StreamingTrainer

        cfg = StreamingConfig(
            batch_size=512, checkpoint_every=5, learning_rate=3e-3, warmup_steps=10
        )
        t = StreamingTrainer(cfg, checkpoint_dir=str(tmp_path / "ck"))
        for i in range(20):
            t.feed(self._rows(cluster, 512, seed=i))
        t.end_of_stream()
        steps = t.run()
        assert steps == 20
        assert t.records_seen == 20 * 512
        assert t.step == 20

        # Resume restores exact step/record counts and params.
        t2 = StreamingTrainer(cfg, checkpoint_dir=str(tmp_path / "ck"))
        assert t2.resume()
        assert t2.step == 20  # checkpoint_every=5 → saved at step 20
        assert t2.records_seen == t.records_seen
        p1 = jax.tree_util.tree_leaves(t.params)
        p2 = jax.tree_util.tree_leaves(t2.params)
        for a, b in zip(p1, p2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_resume_continues_training(self, tmp_path, cluster):
        from dragonfly2_tpu.trainer.streaming import StreamingConfig, StreamingTrainer

        cfg = StreamingConfig(batch_size=256, checkpoint_every=4, warmup_steps=4)
        t = StreamingTrainer(cfg, checkpoint_dir=str(tmp_path / "ck"))
        for i in range(8):
            t.feed(self._rows(cluster, 256, seed=i))
        t.run(idle_timeout=0.1)
        t.checkpoint()

        t2 = StreamingTrainer(cfg, checkpoint_dir=str(tmp_path / "ck"))
        t2.resume()
        start = t2.step
        for i in range(4):
            t2.feed(self._rows(cluster, 256, seed=100 + i))
        t2.end_of_stream()
        t2.run()
        assert t2.step == start + 4
        scorer = t2.export_scorer()
        rows = self._rows(cluster, 500, seed=999)
        pred = scorer.score(rows[:, 2:-1])
        mae = float(np.mean(np.abs(pred - rows[:, -1])))
        assert np.isfinite(mae)

    def test_backpressure(self, cluster):
        from dragonfly2_tpu.trainer.streaming import StreamingConfig, StreamingTrainer

        cfg = StreamingConfig(batch_size=128, queue_capacity=2)
        t = StreamingTrainer(cfg)
        assert t.feed(self._rows(cluster, 128, seed=0), block=False)
        assert t.feed(self._rows(cluster, 128, seed=1), block=False)
        assert not t.feed(self._rows(cluster, 128, seed=2), block=False)


class TestHaloExchange:
    def _local_graph(self, n, shard, rng, locality=0.9, k=8, n_edges=2000):
        """Graph where ~locality of edges stay within a node's shard."""
        dst = rng.integers(0, n, n_edges)
        local = rng.random(n_edges) < locality
        shard_of = dst // shard
        src_local = shard_of * shard + rng.integers(0, shard, n_edges)
        src_any = rng.integers(0, n, n_edges)
        src = np.where(local, src_local, src_any)
        return src.astype(np.int64), dst.astype(np.int64)

    def test_matches_full_aggregation(self):
        from dragonfly2_tpu.parallel.graph_sharding import (
            build_halo_plan,
            halo_neighbor_aggregate,
        )
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = create_mesh()
        n, d, k = 128, 16, 8
        shard = n // mesh.shape["data"]
        rng = np.random.default_rng(7)
        src, dst = self._local_graph(n, shard, rng)
        feats = rng.normal(size=len(src)).astype(np.float32)
        table = build_neighbor_table(n, src, dst, feats, max_neighbors=k)
        h = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))

        # Oracle: plain full aggregation.
        nbr = jnp.take(h, table.indices, axis=0)
        nbr = jnp.concatenate([nbr, table.edge_feats], axis=-1)
        m = table.mask[..., None]
        want = (nbr * m).sum(axis=1) / jnp.maximum(m.sum(axis=1), 1.0)

        plan = build_halo_plan(table, mesh)
        h_sharded = jax.device_put(h, NamedSharding(mesh, P("data")))
        from dragonfly2_tpu.parallel.graph_sharding import make_sharded_table

        t_sharded = make_sharded_table(mesh, table)
        got = halo_neighbor_aggregate(mesh, h_sharded, t_sharded, plan)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

    def test_sharded_precompute_matches_oracle(self):
        """precompute_hop_features_sharded (node-sharded, halo all-to-all
        per hop) equals the replicated precompute — the flagship's
        config[4] precompute path (VERDICT r3 weak-#4)."""
        from dragonfly2_tpu.models.hop import precompute_hop_features
        from dragonfly2_tpu.parallel.graph_sharding import (
            build_halo_plan,
            precompute_hop_features_sharded,
        )

        mesh = create_mesh()
        n, k = 256, 8
        shard = n // mesh.shape["data"]
        rng = np.random.default_rng(11)
        src, dst = self._local_graph(n, shard, rng, locality=0.8, n_edges=4000)
        feats = rng.random(len(src)).astype(np.float32)
        table = build_neighbor_table(n, src, dst, feats, max_neighbors=k)
        nf = rng.normal(size=(n, 12)).astype(np.float32)

        want = precompute_hop_features(jnp.asarray(nf), table, hops=2)
        plan = build_halo_plan(table, mesh)
        got = precompute_hop_features_sharded(
            mesh, jnp.asarray(nf), table, plan, hops=2
        )
        assert got.sharding.spec == jax.sharding.PartitionSpec("data")
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )

    def test_sharded_precompute_rejects_stale_plan(self):
        """A plan built for one table sampling must refuse a different
        table (digest guard), like halo_neighbor_aggregate."""
        import pytest

        from dragonfly2_tpu.parallel.graph_sharding import (
            build_halo_plan,
            precompute_hop_features_sharded,
        )

        mesh = create_mesh()
        n = 64
        rng = np.random.default_rng(3)
        src, dst = self._local_graph(n, n // mesh.shape["data"], rng, n_edges=500)
        table = build_neighbor_table(n, src, dst, max_neighbors=4)
        other = build_neighbor_table(
            n, dst, src, max_neighbors=4
        )  # different sampling
        plan = build_halo_plan(table, mesh)
        nf = jnp.asarray(rng.normal(size=(n, 4)).astype(np.float32))
        with pytest.raises(ValueError, match="different table"):
            precompute_hop_features_sharded(mesh, nf, other, plan)

    def test_halo_smaller_than_shard_with_locality(self):
        from dragonfly2_tpu.parallel.graph_sharding import build_halo_plan

        mesh = create_mesh()
        n = 1024
        shard = n // mesh.shape["data"]
        rng = np.random.default_rng(8)
        src, dst = self._local_graph(n, shard, rng, locality=0.95, n_edges=8000)
        table = build_neighbor_table(n, src, dst, max_neighbors=8)
        plan = build_halo_plan(table, mesh)
        # The exchange ships n_shards*halo rows instead of the full table:
        # with 95% locality the halo must be far below the shard size.
        assert plan.halo < plan.shard_size / 2, (plan.halo, plan.shard_size)


# ---------------------------------------------------------------------------
# DF012 dtype/shape contracts: kernel outputs vs the declared registry
# (dragonfly2_tpu/records/contracts.py) — kernel and contract cannot drift
# apart, for the edge shapes that historically break pads/buckets: empty
# segment sets, a single segment, and bf16 inputs.
# ---------------------------------------------------------------------------


class TestOpsDtypeContracts:
    def _contract(self, key):
        from dragonfly2_tpu.records.contracts import CONTRACTS

        return CONTRACTS[key]

    def test_registry_matches_live_dfc1_columns(self):
        """The declared-once registry and the live featurizer must agree
        on the DFC1 column schema — renaming/reordering/widening a column
        without updating records/contracts.py fails by name here."""
        from dragonfly2_tpu.records.features import DOWNLOAD_COLUMNS, TOPO_COLUMNS

        dl = self._contract("dfc1.download")
        assert list(DOWNLOAD_COLUMNS) == dl["columns"]
        assert np.dtype(dl["dtype"]) == np.float32
        topo = self._contract("dfc1.topology")
        assert list(TOPO_COLUMNS) == topo["columns"]

    def test_registry_matches_columnar_defaults(self):
        from dragonfly2_tpu.records.columnar import ColumnarHeader, ColumnarWriter
        import inspect

        want = self._contract("dfc1.file")["defaults"]
        assert ColumnarHeader(columns=("a",)).dtype == want["ColumnarHeader.dtype"]
        sig = inspect.signature(ColumnarWriter.__init__)
        assert sig.parameters["dtype"].default == \
            want["ColumnarWriter.__init__.dtype"]

    def test_registry_matches_featcache_slot_dtypes(self):
        from dragonfly2_tpu.scheduler.featcache import HostFeatureCache

        cache = HostFeatureCache(max_hosts=8)
        attrs = self._contract("featcache.slots")["attrs"]
        for attr_path, want in attrs.items():
            attr = attr_path.split(".", 1)[1]
            assert getattr(cache, attr).dtype == np.dtype(want), attr_path

    def test_segment_sum_empty_edge_stream(self):
        """Zero edges: every segment must come back an exact zero row of
        the contract dtype (the all-padding block still zero-inits)."""
        want_dtype = np.dtype(self._contract("ops.segment_sum")["dtype"])
        vals = np.zeros((0, 8), np.float32)
        ids = np.zeros(0, np.int64)
        out = np.asarray(
            segment_sum_pallas(jnp.asarray(vals), ids, 64, interpret=True)
        )
        assert out.shape == (64, 8)
        assert out.dtype == want_dtype
        assert not out.any()

    def test_segment_sum_single_segment(self):
        """Every edge lands in one segment: sum parity with numpy and the
        contract dtype, others exactly zero."""
        want_dtype = np.dtype(self._contract("ops.segment_sum")["dtype"])
        rng = np.random.default_rng(3)
        vals = rng.normal(size=(37, 8)).astype(np.float32)
        ids = np.full(37, 5, np.int64)
        out = np.asarray(
            segment_sum_pallas(jnp.asarray(vals), ids, 16, exact=True,
                               interpret=True)
        )
        assert out.dtype == want_dtype
        np.testing.assert_allclose(out[5], vals.sum(axis=0), rtol=1e-5)
        mask = np.ones(16, bool)
        mask[5] = False
        assert not out[mask].any()

    def test_segment_sum_bf16_values_accumulate_f32(self):
        """bf16 values (the allowed native-MXU mode) must still ACCUMULATE
        and return in the contract float32 — the allow-list covers the
        multiplicand cast, never the output."""
        c = self._contract("ops.segment_sum")
        assert "bfloat16" in c["allow"]
        rng = np.random.default_rng(4)
        vals = rng.normal(size=(64, 8)).astype(np.float32)
        ids = rng.integers(0, 10, 64)
        out = np.asarray(
            segment_sum_pallas(
                jnp.asarray(vals, jnp.bfloat16), ids, 10, exact=False,
                interpret=True,
            )
        )
        assert out.dtype == np.dtype(c["dtype"])
        want = np.asarray(segment_sum(jnp.asarray(vals), jnp.asarray(ids), 10))
        np.testing.assert_allclose(out, want, rtol=3e-2, atol=3e-2)


class TestFusedGatherScore:
    """ops/pallas_score.py: fused slot-row gather + mask-folded MLP
    scoring over the columnar host store (DESIGN.md §18) — jnp fallback,
    the real pallas kernel in interpret mode, and the rule-arm matvec."""

    def _weights(self, seed=0, dims=(32, 64, 64, 1)):
        rng = np.random.default_rng(seed)
        return [
            (
                rng.standard_normal((dims[i], dims[i + 1])).astype(np.float32) * 0.3,
                rng.standard_normal(dims[i + 1]).astype(np.float32) * 0.05,
            )
            for i in range(len(dims) - 1)
        ]

    def _serving(self, n_hosts=120, seed=3, max_hosts=512):
        from dragonfly2_tpu.scheduler import HostFeatureCache, MLEvaluator
        from dragonfly2_tpu.sim.swarm import build_announce_swarm
        from dragonfly2_tpu.trainer.export import MLPScorer

        task, peers = build_announce_swarm(n_hosts, seed=seed)
        cache = HostFeatureCache(max_hosts=max_hosts)
        weights = self._weights(seed)
        ref = MLPScorer(weights=weights)
        ml_ref = MLEvaluator(ref, feature_cache=cache)
        return task, peers, cache, weights, ref, ml_ref

    def test_fused_fallback_ordering_equals_numpy_scorer(self):
        from dragonfly2_tpu.ops.pallas_score import FusedMLPScorer
        from dragonfly2_tpu.scheduler import MLEvaluator

        task, peers, cache, weights, ref, ml_ref = self._serving()
        fused = FusedMLPScorer(cache, weights, use_pallas=False)
        ml_fused = MLEvaluator(fused, feature_cache=cache)
        rng = np.random.default_rng(11)
        for _ in range(12):
            ci = int(rng.integers(0, len(peers)))
            cand = [int(c) if c < ci else int(c) + 1
                    for c in rng.choice(len(peers) - 1, size=24, replace=False)]
            child, parents = peers[ci], [peers[c] for c in cand]
            a = [p.id for p in ml_ref.evaluate_parents(
                parents, child, task.total_piece_count)]
            b = [p.id for p in ml_fused.evaluate_parents(
                parents, child, task.total_piece_count)]
            assert a == b

    def test_pallas_kernel_interpret_matches_fallback(self):
        from dragonfly2_tpu.ops.pallas_score import FusedMLPScorer

        task, peers, cache, weights, ref, ml_ref = self._serving(n_hosts=60)
        # Bind everyone, then score the same slots through both modes.
        cache.gather([p.host for p in peers])
        fb = FusedMLPScorer(cache, weights, use_pallas=False)
        kern = FusedMLPScorer(cache, weights, use_pallas=True, interpret=True,
                              cand_block=8)
        edge, slots, cslot, _, _ = ml_ref._featurize_slots(
            peers[1:25], peers[0]
        )
        dst = np.full(len(slots), cslot, dtype=np.int64)
        a = fb.score(edge, src_buckets=slots, dst_buckets=dst)
        b = kern.score(edge, src_buckets=slots, dst_buckets=dst)
        assert a.dtype == b.dtype == np.float32
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
        # And both agree with the numpy serving scorer to float tolerance
        # (sum order differs across the three partial matmuls).
        feats, _, _ = ml_ref._featurize_batch(peers[1:25], peers[0])
        want = ref.score(feats)
        np.testing.assert_allclose(a, want, rtol=1e-4, atol=1e-4)

    def test_mask_folding_post_hoc_columns_have_no_effect(self):
        from dragonfly2_tpu.ops.pallas_score import fold_post_hoc_weights
        from dragonfly2_tpu.records.features import POST_HOC_FEATURE_IDX
        from dragonfly2_tpu.trainer.export import MLPScorer

        weights = self._weights(5)
        folded = fold_post_hoc_weights(weights)
        for i in POST_HOC_FEATURE_IDX:
            assert np.all(folded[0][0][i] == 0.0)
        rng = np.random.default_rng(5)
        x = rng.standard_normal((16, 32)).astype(np.float32)
        x2 = np.array(x, copy=True)
        x2[:, list(POST_HOC_FEATURE_IDX)] = rng.standard_normal(
            (16, len(POST_HOC_FEATURE_IDX))
        ).astype(np.float32)
        s = MLPScorer(weights=folded, post_hoc_masked=False)
        assert np.array_equal(s.score(x), s.score(x2))

    def test_padding_rows_do_not_bleed(self):
        from dragonfly2_tpu.ops.pallas_score import FusedMLPScorer

        task, peers, cache, weights, ref, ml_ref = self._serving(n_hosts=40)
        cache.gather([p.host for p in peers])
        fused = FusedMLPScorer(cache, weights, use_pallas=False, cand_block=16)
        edge, slots, cslot, _, _ = ml_ref._featurize_slots(peers[1:8], peers[0])
        dst = np.full(len(slots), cslot, dtype=np.int64)
        a = fused.score(edge, src_buckets=slots, dst_buckets=dst)   # n=7 → pad 16
        assert a.shape == (7,)
        # Same rows inside a differently-padded call score identically.
        edge2, slots2, cslot2, _, _ = ml_ref._featurize_slots(
            peers[1:20], peers[0]
        )
        dst2 = np.full(len(slots2), cslot2, dtype=np.int64)
        b = fused.score(edge2, src_buckets=slots2, dst_buckets=dst2)[:7]
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)

    def test_mirror_resyncs_on_column_writes(self):
        from dragonfly2_tpu.ops.pallas_score import FusedMLPScorer

        task, peers, cache, weights, ref, ml_ref = self._serving(n_hosts=30)
        cache.gather([p.host for p in peers])
        fused = FusedMLPScorer(cache, weights, use_pallas=False)
        edge, slots, cslot, _, _ = ml_ref._featurize_slots(peers[1:9], peers[0])
        dst = np.full(len(slots), cslot, dtype=np.int64)
        before = fused.score(edge, src_buckets=slots, dst_buckets=dst)
        ver = fused._mat_version
        # Announce-path write-through moves the store's row version; the
        # next flush re-uploads the mirror and the scores move.
        for p in peers[1:9]:
            p.host.upload_count += 50
        after = fused.score(edge, src_buckets=slots, dst_buckets=dst)
        assert fused._mat_version != ver
        assert not np.array_equal(before, after)

    def test_from_scorer_rejects_standardized_artifacts(self):
        from dragonfly2_tpu.ops.pallas_score import FusedMLPScorer
        from dragonfly2_tpu.scheduler import HostFeatureCache
        from dragonfly2_tpu.trainer.export import MLPScorer

        s = MLPScorer(
            weights=self._weights(1),
            feat_mean=np.zeros(32, np.float32),
            feat_std=np.ones(32, np.float32),
        )
        with pytest.raises(ValueError):
            FusedMLPScorer.from_scorer(HostFeatureCache(max_hosts=8), s)

    def test_rule_weighted_sum_matches_numpy(self):
        from dragonfly2_tpu.ops.pallas_score import (
            RULE_COMPONENT_WEIGHTS,
            rule_weighted_sum,
        )

        rng = np.random.default_rng(9)
        comp = rng.standard_normal((37, 6)).astype(np.float32)
        want = comp @ np.asarray(RULE_COMPONENT_WEIGHTS, np.float32)
        got_fb = rule_weighted_sum(comp, use_pallas=False)
        got_kern = rule_weighted_sum(comp, interpret=True)
        assert got_fb.dtype == got_kern.dtype == np.float32
        np.testing.assert_allclose(got_fb, want, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(got_kern, want, rtol=1e-6, atol=1e-6)

    def test_quantized_scorer_dtypes_and_roundtrip(self):
        """The int8/bf16 quantized blob (scorer.quantized contract):
        payload dtypes, scale stamping next to drift histograms, exact
        dequantized-score roundtrip through the blob."""
        from dragonfly2_tpu.trainer.export import (
            MLPScorer,
            QuantizedMLPScorer,
            feature_snapshot_stats,
            load_scorer,
            quantize_scorer,
            scorer_to_bytes,
        )

        rng = np.random.default_rng(4)
        rows = rng.standard_normal((400, 32)).astype(np.float32)
        edges, fracs = feature_snapshot_stats(rows)
        base = MLPScorer(weights=self._weights(4), train_bin_edges=edges,
                         train_bin_fracs=fracs)
        want = base.score(rows)
        for mode, payload_dtype in (("int8", np.int8), ("bf16", np.uint16)):
            q = quantize_scorer(base, mode)
            assert q.model_type == f"mlp_{mode}"
            for payload, scale in q.qlayers:
                assert payload.dtype == payload_dtype
                if mode == "int8":
                    assert scale.dtype == np.float32
            for w, b in q.weights:
                assert w.dtype == np.float32 and b.dtype == np.float32
            got = q.score(rows)
            rel = np.max(np.abs(got - want)) / np.max(np.abs(want))
            assert rel < 0.05  # quantization error is bounded, not zero
            q2 = load_scorer(scorer_to_bytes(q))
            assert isinstance(q2, QuantizedMLPScorer)
            assert q2.quant_mode == mode
            assert np.array_equal(q2.score(rows), got)  # blob-exact
            assert np.array_equal(q2.train_bin_edges, edges)  # scales ride
            assert np.array_equal(q2.train_bin_fracs, fracs)  # w/ the drift baseline
