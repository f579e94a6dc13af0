"""Online graph trainer (BASELINE configs[5]): two-stream ingest,
mid-training snapshot refresh, byte-identical resume across a refresh
boundary (trainer/online_graph.py; reference stream demux
trainer/service/service_v1.go:128-143)."""

import numpy as np
import pytest

from dragonfly2_tpu.models.hop import HopConfig
from dragonfly2_tpu.records.synthetic import SyntheticCluster
from dragonfly2_tpu.trainer.online_graph import (
    OnlineGraphConfig,
    OnlineGraphTrainer,
    state_hash,
)
from dragonfly2_tpu.trainer.train import TrainConfig

N_NODES = 128


def _mk_cluster(seed=0):
    return SyntheticCluster(num_hosts=N_NODES, seed=seed)


def _topo(cluster, seed):
    rng = np.random.default_rng(seed)
    n = N_NODES * 8
    src = rng.integers(0, N_NODES, n)
    dst = rng.integers(0, N_NODES, n)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    # Deterministic rtt (no shared-rng draw) for replayable streams.
    return src, dst, (cluster._rtt_vec(src, dst, noise=False) / 1e9).astype(
        np.float32
    )


def _downloads(cluster, seed, n):
    rng = np.random.default_rng(seed)
    es = rng.integers(0, N_NODES, n).astype(np.int32)
    ed = (es + rng.integers(1, N_NODES, n).astype(np.int32)) % N_NODES
    y = np.log1p(cluster._bandwidth_vec(es, ed, rng=rng)).astype(np.float32)
    return es, ed, y


def _mk_trainer(cluster, tmp_path=None, **cfg_kw):
    defaults = dict(
        num_nodes=N_NODES,
        max_neighbors=8,
        batch_size=256,
        super_steps=4,
        queue_capacity=16,  # tests feed the whole stream before run()
        model=HopConfig(hidden=16, out_dim=8, node_embed_dim=4, dropout=0.1),
        train=TrainConfig(warmup_steps=2),
        total_steps_hint=1000,
    )
    defaults.update(cfg_kw)
    cfg = OnlineGraphConfig(**defaults)
    src, dst, rtt = _topo(cluster, seed=1)
    return OnlineGraphTrainer(
        cfg,
        node_feats=cluster._host_feature_matrix(),
        topo_src=src, topo_dst=dst, topo_rtt=rtt,
        checkpoint_dir=str(tmp_path) if tmp_path else None,
    )


def _state_hash(trainer) -> str:
    return state_hash(trainer.state)


class TestSnapshotRefresh:
    def test_swap_changes_graph_not_optimizer(self):
        import jax

        cluster = _mk_cluster()
        tr = _mk_trainer(cluster)
        es, ed, y = _downloads(cluster, 2, 4 * 256 * 2)
        tr.feed_downloads(es, ed, y)
        assert tr.run(max_dispatches=2, idle_timeout=0.1) == 2
        compiles_before = tr._dispatch_fn._cache_size()
        step_before = int(tr.state.step)
        params_before = jax.tree_util.tree_map(np.asarray, tr.state.params)
        digest_before = tr.snapshot_digest()

        # New topology (drifted load) → refresh swaps the hop tables only.
        cluster.drift(np.random.default_rng(7))
        tr.set_node_features(cluster._host_feature_matrix())
        src, dst, rtt = _topo(cluster, seed=9)
        tr.feed_topology(src, dst, rtt)
        assert tr.refresh_snapshot() is not None
        assert tr.snapshot_digest() != digest_before
        assert tr.snapshot_idx == 1
        assert int(tr.state.step) == step_before  # optimizer untouched
        for a, b in zip(
            jax.tree_util.tree_leaves(params_before),
            jax.tree_util.tree_leaves(tr.state.params),
        ):
            np.testing.assert_array_equal(a, np.asarray(b))

        # Training continues on the new snapshot with the SAME compiled
        # program (hop tables are arguments, shapes static).
        tr.feed_downloads(*_downloads(cluster, 3, 4 * 256))
        assert tr.run(max_dispatches=1, idle_timeout=0.1) == 1
        assert int(tr.state.step) == step_before + 4
        assert compiles_before == 1, "steady-state dispatch recompiled"
        assert tr._dispatch_fn._cache_size() == compiles_before, (
            "snapshot swap recompiled"
        )

    def test_refresh_with_no_new_topology_keeps_old_graph(self):
        """The bootstrap feed belongs to snapshot 0 — with no probes since,
        a refresh keeps serving the old graph instead of paying a rebuild
        for an identical one."""
        cluster = _mk_cluster()
        tr = _mk_trainer(cluster, topo_window=100)
        digest = tr.snapshot_digest()
        assert tr.refresh_snapshot() is None
        assert tr.snapshot_digest() == digest
        assert tr.snapshot_idx == 0
        # New probes arrive → the next refresh swaps.
        tr.feed_topology(*_topo(cluster, seed=77))
        assert tr.refresh_snapshot() is not None
        assert tr.snapshot_idx == 1

    def test_topology_window_trims_oldest(self):
        cluster = _mk_cluster()
        tr = _mk_trainer(cluster, topo_window=500)
        for seed in range(5):
            src, dst, rtt = _topo(cluster, seed=seed)
            tr.feed_topology(src, dst, rtt)
        src, dst, rtt = tr._drain_window()
        assert len(src) <= 500
        # The window holds the MOST RECENT edges (tail of the last feed).
        last_src, _, _ = _topo(cluster, seed=4)
        np.testing.assert_array_equal(src[-len(last_src):], last_src[-len(src):])


class TestResumeAcrossRefresh:
    def test_byte_identical_resume_across_refresh_boundary(self, tmp_path):
        """Kill after a swap, resume, continue → same bytes as the
        uninterrupted run (the r3 soak's proof, now with a mid-stream
        graph swap in the window)."""
        def feed_all(tr, cluster):
            # Deterministic two-stream schedule: topology for snapshot 1
            # arrives before dispatch 2's refresh.
            src, dst, rtt = _topo(cluster, seed=100)
            tr.feed_topology(src, dst, rtt)
            for d in range(4):
                tr.feed_downloads(*_downloads(cluster, 50 + d, 4 * 256))

        # Run A: uninterrupted, refresh every 2 dispatches.
        ca = _mk_cluster()
        a = _mk_trainer(ca, tmp_path / "a", refresh_every=2)
        feed_all(a, ca)
        assert a.run(max_dispatches=4, idle_timeout=0.1) == 4
        assert a.snapshot_idx >= 1

        # Run B: same stream, checkpoint at dispatch 3 (PAST the refresh
        # at 2), then a fresh process resumes and finishes.
        cb = _mk_cluster()
        b = _mk_trainer(cb, tmp_path / "b", refresh_every=2)
        feed_all(b, cb)
        assert b.run(max_dispatches=3, idle_timeout=0.1) == 3
        assert b.snapshot_idx >= 1  # the boundary is behind the checkpoint
        b.checkpoint()
        del b

        cc = _mk_cluster()
        c = _mk_trainer(cc, tmp_path / "b", refresh_every=2)
        assert c.resume()
        assert c.dispatch == 3 and c.snapshot_idx >= 1
        # Rebuilt snapshot must equal run A's post-refresh snapshot.
        assert c.snapshot_digest() == a.snapshot_digest()
        c.feed_downloads(*_downloads(cc, 53, 4 * 256))
        assert c.run(max_dispatches=1, idle_timeout=0.1) == 1
        assert _state_hash(c) == _state_hash(a)

    def test_resume_without_checkpoint_returns_false(self, tmp_path):
        cluster = _mk_cluster()
        tr = _mk_trainer(cluster, tmp_path / "none")
        assert not tr.resume()


class TestWireIngest:
    """The Train stream feeds the online trainer DIRECTLY (VERDICT r3's
    configs[5] wire story): chunks decode incrementally mid-stream and
    rows reach the train loop before EOF."""

    def test_streaming_decoder_matches_reader_at_awkward_splits(self, tmp_path):
        from dragonfly2_tpu.records.columnar import (
            ColumnarReader,
            ColumnarWriter,
            StreamingRowDecoder,
        )

        path = str(tmp_path / "s.dfc")
        rng = np.random.default_rng(0)
        want = rng.random((257, 7)).astype(np.float32)
        with ColumnarWriter(path, tuple(f"c{i}" for i in range(7))) as w:
            w.append(want)
        blob = open(path, "rb").read()
        # Splits that straddle the magic, the header, and row boundaries.
        dec = StreamingRowDecoder()
        pos = 0
        parts = []
        for cut in (2, 5, 11, 64, 300, 301):
            parts.append(blob[pos:cut])
            pos = cut
        parts.append(blob[pos:])
        chunks = [dec.feed(p) for p in parts]
        rows = np.concatenate([c for c in chunks if c.size], axis=0)
        np.testing.assert_array_equal(rows, ColumnarReader(path).to_array())
        assert dec.rows_decoded == 257

        # Fixed-size chunker whose boundary NEVER aligns with rows (the
        # gRPC framing shape): every split row reassembles exactly once.
        dec2 = StreamingRowDecoder()
        got2 = [
            dec2.feed(blob[i : i + 1000]) for i in range(0, len(blob), 1000)
        ]
        rows2 = np.concatenate([c for c in got2 if c.size], axis=0)
        np.testing.assert_array_equal(rows2, ColumnarReader(path).to_array())

    def test_train_stream_feeds_online_trainer(self, tmp_path):
        """Wire e2e: shards stream over the real Train HTTP transport;
        the online trainer consumes edges and refreshes its graph from
        the WIRE-fed topology."""
        from dragonfly2_tpu.records.columnar import ColumnarWriter
        from dragonfly2_tpu.records.features import DOWNLOAD_COLUMNS, TOPO_COLUMNS
        from dragonfly2_tpu.rpc.trainer_transport import (
            RemoteTrainer,
            TrainerHTTPServer,
        )
        from dragonfly2_tpu.trainer.service import TrainerService

        cluster = _mk_cluster()
        tr = _mk_trainer(cluster)
        adapter = tr.make_wire_adapter()
        service = TrainerService(
            data_dir=str(tmp_path / "stage"), online_sink=adapter
        )
        # Ingest-only here: EOF batch retraining has its own tests.
        service._run_training = lambda run, session: run.done.set()

        # Download shard: bucket-space rows from the synthetic swarm.
        dl = cluster.generate_feature_rows(4 * 256 * 3, seed=5)
        dl_path = str(tmp_path / "dl.dfc")
        with ColumnarWriter(dl_path, DOWNLOAD_COLUMNS) as w:
            w.append(dl)
        # Topology shard: probe edges in the SAME bucket space.
        buckets = cluster._bucket_table()
        src, dst, rtt = _topo(cluster, seed=8)
        topo = np.zeros((len(src), len(TOPO_COLUMNS)), np.float32)
        topo[:, 0] = buckets[src]
        topo[:, 1] = buckets[dst]
        topo[:, 2] = rtt
        topo_path = str(tmp_path / "topo.dfc")
        with ColumnarWriter(topo_path, TOPO_COLUMNS) as w:
            w.append(topo)

        server = TrainerHTTPServer(service)
        server.serve()
        try:
            client = RemoteTrainer(server.url)
            session = client.open_train_stream(
                ip="10.0.0.7", hostname="wire-online", scheduler_id="s"
            )
            session.send_download_shard(dl_path)
            session.send_network_topology_shard(topo_path)
        finally:
            server.stop()

        assert adapter.overflow_edges == 0
        # Edges reached the trainer off the WIRE: a dispatch runs...
        assert tr.run(max_dispatches=2, idle_timeout=0.5) == 2
        assert tr.records_seen == 2 * 4 * 256
        # ...and the wire-fed topology builds the NEXT snapshot.
        digest = tr.snapshot_digest()
        assert tr.refresh_snapshot() is not None
        assert tr.snapshot_digest() != digest


    def test_reconnect_resend_feeds_rows_once(self, tmp_path):
        """A client that reconnects and resends a shard (fresh session,
        empty chunk_seq) must not double-feed the sink — the service
        dedupes on a per-dataset row high-water mark."""
        from dragonfly2_tpu.records.columnar import ColumnarWriter
        from dragonfly2_tpu.records.features import DOWNLOAD_COLUMNS
        from dragonfly2_tpu.trainer.service import TrainerService

        class Sink:
            def __init__(self):
                self.download_rows = 0
                self.topology_rows = 0

            def feed_download_rows(self, rows):
                self.download_rows += len(rows)

            def feed_topology_rows(self, rows):
                self.topology_rows += len(rows)

        sink = Sink()
        service = TrainerService(
            data_dir=str(tmp_path / "stage"), online_sink=sink
        )
        path = str(tmp_path / "d.dfc")
        with ColumnarWriter(path, DOWNLOAD_COLUMNS) as w:
            w.append(np.random.default_rng(0).random(
                (100, len(DOWNLOAD_COLUMNS))).astype(np.float32))
        blob = open(path, "rb").read()

        s1 = service.open_train_stream(ip="1.2.3.4", hostname="h", scheduler_id="s")
        service.receive_shard_bytes(s1, "download", "d.dfc", blob, seq=0)
        assert sink.download_rows == 100
        # Reconnect: fresh session, SAME shard resent from scratch.
        s2 = service.open_train_stream(ip="1.2.3.4", hostname="h", scheduler_id="s")
        service.receive_shard_bytes(s2, "download", "d.dfc", blob, seq=0)
        assert sink.download_rows == 100  # not 200
        # A LONGER resend (shard grew) feeds only the new tail.
        with ColumnarWriter(str(tmp_path / "d2.dfc"), DOWNLOAD_COLUMNS) as w:
            w.append(np.random.default_rng(0).random(
                (130, len(DOWNLOAD_COLUMNS))).astype(np.float32))
        blob2 = open(str(tmp_path / "d2.dfc"), "rb").read()
        s3 = service.open_train_stream(ip="1.2.3.4", hostname="h", scheduler_id="s")
        service.receive_shard_bytes(s3, "download", "d.dfc", blob2, seq=0)
        assert sink.download_rows == 130

    def test_online_mode_tolerates_reference_csv(self, tmp_path):
        """A legacy CSV shard on the wire (the compat path) must not
        crash online mode — it skips online decode and stages normally."""
        from dragonfly2_tpu.trainer.service import TrainerService

        class Sink:
            def feed_download_rows(self, rows):
                raise AssertionError("CSV must not online-decode")

            feed_topology_rows = feed_download_rows

        service = TrainerService(
            data_dir=str(tmp_path / "stage"), online_sink=Sink()
        )
        s = service.open_train_stream(ip="1.2.3.4", hostname="h", scheduler_id="s")
        service.receive_shard_bytes(
            s, "download", "legacy.csv", b"a,b,c\n1,2,3\n", seq=0
        )
        assert len(s.download_shards) == 1  # staged for batch conversion


class TestOnlineMeshMode:
    """config[4]×[5]: the ONLINE trainer on a (data, model) mesh — node
    tables AND the snapshot precompute sharded over the model axis."""

    def _mk(self, cluster, tmp_path=None, **kw):
        from dragonfly2_tpu.parallel.mesh import MeshSpec, create_mesh

        mesh = create_mesh(MeshSpec(data=4, model=2))
        return _mk_trainer(
            cluster, tmp_path, mesh=mesh, node_sharding="model", **kw
        )

    def test_matches_replicated_and_swaps_without_recompile(self, tmp_path):
        import jax

        from dragonfly2_tpu.parallel.mesh import MeshSpec, create_mesh

        # The reference arm has NO mesh: one device, nothing sharded.
        cluster_a = _mk_cluster()
        repl = _mk_trainer(cluster_a)
        cluster_b = _mk_cluster()
        mp = self._mk(cluster_b)
        # Plain data parallelism: node tables whole on every device.
        cluster_c = _mk_cluster()
        dp = _mk_trainer(
            cluster_c, mesh=create_mesh(MeshSpec(data=8)),
            node_sharding="replicated",
        )

        for tr, cl in ((repl, cluster_a), (mp, cluster_b), (dp, cluster_c)):
            tr.feed_downloads(*_downloads(cl, 7, 4 * 256 * 2))
            assert tr.run(max_dispatches=2, idle_timeout=0.1) == 2
        # Same stream, same seeds: both mesh programs compute the
        # single-device training result to float tolerance.
        v = _downloads(cluster_a, 99, 1024)
        ref_mae = repl.eval_mae(*v)
        assert abs(ref_mae - mp.eval_mae(*v)) < 5e-3
        assert abs(ref_mae - dp.eval_mae(*v)) < 5e-3
        assert abs(float(repl.last_loss) - float(mp.last_loss)) < 5e-3
        assert abs(float(repl.last_loss) - float(dp.last_loss)) < 5e-3
        # The hop tables live SHARDED over the model axis.
        from jax.sharding import PartitionSpec as P

        assert mp.hop_feats.sharding.spec == P("model")
        # Replicated on a mesh means on EVERY device of it — state and
        # snapshot alike — not committed to device 0.
        for leaf in jax.tree_util.tree_leaves(
            (dp.state.params, dp.hop_feats, dp.table)
        ):
            assert len(leaf.devices()) == 8

        # Snapshot swap on the mesh: sharded precompute re-runs, the
        # compiled dispatch is reused.
        compiles = mp._dispatch_fn._cache_size()
        cluster_b.drift(np.random.default_rng(3))
        mp.set_node_features(cluster_b._host_feature_matrix())
        mp.feed_topology(*_topo(cluster_b, seed=31))
        assert mp.refresh_snapshot() is not None
        mp.feed_downloads(*_downloads(cluster_b, 8, 4 * 256))
        assert mp.run(max_dispatches=1, idle_timeout=0.1) == 1
        assert mp._dispatch_fn._cache_size() == compiles

    def test_mesh_resume_across_refresh(self, tmp_path):
        def feed(tr, cl):
            tr.feed_topology(*_topo(cl, seed=100))
            for d in range(3):
                tr.feed_downloads(*_downloads(cl, 60 + d, 4 * 256))

        ca = _mk_cluster()
        a = self._mk(ca, tmp_path / "a", refresh_every=2)
        feed(a, ca)
        assert a.run(max_dispatches=3, idle_timeout=0.1) == 3
        assert a.snapshot_idx >= 1

        cb = _mk_cluster()
        b = self._mk(cb, tmp_path / "b", refresh_every=2)
        feed(b, cb)
        assert b.run(max_dispatches=2, idle_timeout=0.1) == 2
        b.checkpoint()
        del b
        cc = _mk_cluster()
        c = self._mk(cc, tmp_path / "b", refresh_every=2)
        assert c.resume()
        assert c.dispatch == 2 and c.snapshot_idx >= 1
        c.feed_downloads(*_downloads(cc, 62, 4 * 256))
        assert c.run(max_dispatches=1, idle_timeout=0.1) == 1
        assert _state_hash(c) == _state_hash(a)

    def test_bad_configs_rejected(self):
        from dragonfly2_tpu.parallel.mesh import MeshSpec, create_mesh

        cluster = _mk_cluster()
        with pytest.raises(ValueError, match="needs a mesh"):
            _mk_trainer(cluster, node_sharding="model")
        with pytest.raises(ValueError, match="unknown node_sharding"):
            _mk_trainer(cluster, node_sharding="bogus")
        mesh = create_mesh(MeshSpec(data=4, model=2))
        with pytest.raises(ValueError, match="not divisible"):
            _mk_trainer(
                cluster, mesh=mesh, node_sharding="model", batch_size=254
            )


class TestOnlineQuality:
    def test_refresh_tracks_drift_better_than_stale(self):
        """After load drift, FRESH hop features beat STALE ones on new
        downloads — the evidence that the mid-training refresh loop
        matters (configs[5]'s defining property)."""
        cluster = _mk_cluster()
        tr = _mk_trainer(cluster)
        # Train a while on the initial graph.
        for d in range(6):
            tr.feed_downloads(*_downloads(cluster, 200 + d, 4 * 256))
        assert tr.run(max_dispatches=6, idle_timeout=0.1) == 6

        # Drift the cluster hard (several epochs of load churn).
        rng = np.random.default_rng(42)
        for _ in range(5):
            cluster.drift(rng)

        v_es, v_ed, v_y = _downloads(cluster, 999, 2048)
        stale = tr.eval_mae(v_es, v_ed, v_y)

        tr.set_node_features(cluster._host_feature_matrix())
        tr.feed_topology(*_topo(cluster, seed=300))
        tr.refresh_snapshot()
        # Adapt briefly on post-drift downloads, then eval fresh.
        for d in range(4):
            tr.feed_downloads(*_downloads(cluster, 400 + d, 4 * 256))
        tr.run(max_dispatches=4, idle_timeout=0.1)
        fresh = tr.eval_mae(v_es, v_ed, v_y)
        assert fresh < stale, (fresh, stale)


class TestNodeLifecycle:
    """node_ttl > 0: TTL eviction + dense-id recycling in the wire
    adapter (reference host GC semantics, scheduler/config/config.go:
    176-197) — churn past capacity must not permanently freeze the
    trainer on the early-arrivals subgraph."""

    @staticmethod
    def _rows(src_b, dst_b, rng):
        from dragonfly2_tpu.records.features import DOWNLOAD_COLUMNS

        n = len(src_b)
        rows = rng.random((n, len(DOWNLOAD_COLUMNS))).astype(np.float32)
        rows[:, 0] = src_b
        rows[:, 1] = dst_b
        rows[:, -1] = np.log1p(rng.random(n).astype(np.float32) * 50.0)
        return rows

    @staticmethod
    def _embedding_leaves(tree):
        import jax

        out = []

        def f(path, leaf):
            if any(getattr(p, "key", None) == "embedding" for p in path):
                out.append(np.asarray(leaf))
            return leaf

        jax.tree_util.tree_map_with_path(f, tree)
        return out

    def test_churn_3x_capacity_recycles_without_permanent_drops(self):
        cluster = _mk_cluster()
        tr = _mk_trainer(cluster, node_ttl=10.0, native_ingest=False)
        ad = tr.make_wire_adapter()
        t = {"now": 0.0}
        ad.clock = lambda: t["now"]
        rng = np.random.default_rng(0)

        def phase_buckets(phase):
            return np.arange(N_NODES, dtype=np.int64) + 10_000 * (phase + 1)

        def feed_phase(phase):
            b = phase_buckets(phase)
            for _ in range(3):
                ad.feed_download_rows(self._rows(b, np.roll(b, 1), rng))
                t["now"] += 1.0

        # Phase 0 fills the table exactly; train so embeddings/moments
        # are live (recycling must provably clear them later).
        feed_phase(0)
        assert ad._next_id == N_NODES and ad.overflow_edges == 0
        tr.feed_downloads(*_downloads(cluster, 5, 4 * 256 * 2))
        assert tr.run(max_dispatches=2, idle_timeout=0.1) == 2

        # Full table + nothing expired: the drop is transient, counted
        # on the adapter AND in the prometheus registry.
        from dragonfly2_tpu.trainer.metrics import ONLINE_OVERFLOW_EDGES

        metric_before = ONLINE_OVERFLOW_EDGES.value()
        extra = np.array([999_999], dtype=np.int64)
        ad.feed_download_rows(self._rows(extra, phase_buckets(0)[:1], rng))
        assert ad.overflow_edges == 1
        assert ONLINE_OVERFLOW_EDGES.value() == metric_before + 1

        # Keep two phase-0 hosts warm via the TOPOLOGY stream at t=20...
        t["now"] = 20.0
        ad.feed_topology_rows(
            np.array([[10_000, 10_001, 0.01]], dtype=np.float32)
        )
        survivors = [int(ad._id_table[10_000]), int(ad._id_table[10_001])]

        # ...then a new host wave at t=25: everything else expired.
        t["now"] = 25.0
        b1 = phase_buckets(1)[: N_NODES - 2]
        ad.feed_download_rows(self._rows(b1, np.roll(b1, 1), rng))
        assert ad.evicted_nodes == N_NODES - 2
        assert ad.overflow_edges == 1  # eviction freed capacity: no new drops
        assert all(int(ad._id_table[b]) >= 0 for b in b1)

        # Row resets: evicted embedding rows AND moments zero; the two
        # survivors keep their learned state.
        n_reset = tr.apply_pending_recycles()
        assert n_reset == N_NODES - 2 and tr.nodes_recycled == N_NODES - 2
        evicted_mask = np.ones(N_NODES, bool)
        evicted_mask[survivors] = False
        param_leaves = self._embedding_leaves(tr.state.params)
        moment_leaves = self._embedding_leaves(tr.state.opt_state)
        assert param_leaves and moment_leaves
        for leaf in param_leaves + moment_leaves:
            assert not leaf[evicted_mask].any(), "recycled row not reset"
        assert all(
            np.abs(leaf[survivors]).sum() > 0 for leaf in param_leaves
        ), "survivor embedding clobbered"

        # The host dropped at capacity returns once ids free again —
        # drops are transient, never permanent.
        t["now"] = 40.0
        ad.feed_download_rows(self._rows(extra, phase_buckets(1)[:1], rng))
        assert int(ad._id_table[999_999]) >= 0
        assert ad.evicted_nodes >= N_NODES  # second wave ran

        # Training continues across recycling: loss/eval finite.
        tr.apply_pending_recycles()
        tr.feed_downloads(*_downloads(cluster, 6, 4 * 256))
        assert tr.run(max_dispatches=1, idle_timeout=0.1) == 1
        v = tr.eval_mae(*_downloads(cluster, 7, 512))
        assert np.isfinite(v)

    def test_ttl_zero_keeps_frozen_first_come_mapping(self):
        """The default stays byte-deterministic: no eviction, overflow
        drops are permanent, the original mapping is never disturbed."""
        cluster = _mk_cluster()
        tr = _mk_trainer(cluster, native_ingest=False)  # node_ttl defaults to 0
        ad = tr.make_wire_adapter()
        t = {"now": 0.0}
        ad.clock = lambda: t["now"]
        rng = np.random.default_rng(1)
        b0 = np.arange(N_NODES, dtype=np.int64) + 10_000
        ad.feed_download_rows(self._rows(b0, np.roll(b0, 1), rng))
        mapping = ad._id_table[b0].copy()
        t["now"] = 1e9  # far beyond any ttl
        extra = np.array([999_999], dtype=np.int64)
        ad.feed_download_rows(self._rows(extra, b0[:1], rng))
        assert int(ad._id_table[999_999]) == -1  # permanent drop
        assert ad.evicted_nodes == 0
        np.testing.assert_array_equal(ad._id_table[b0], mapping)
        assert tr.apply_pending_recycles() == 0

    def test_dropped_host_alone_reclaims_expired_capacity(self):
        """A -1-memoized host must itself trigger eviction when it
        returns after capacity expired — transience cannot depend on a
        brand-new bucket arriving to kick the slow path."""
        cluster = _mk_cluster()
        tr = _mk_trainer(cluster, node_ttl=10.0, native_ingest=False)
        ad = tr.make_wire_adapter()
        t = {"now": 0.0}
        ad.clock = lambda: t["now"]
        rng = np.random.default_rng(2)
        b0 = np.arange(N_NODES, dtype=np.int64) + 10_000
        ad.feed_download_rows(self._rows(b0, np.roll(b0, 1), rng))
        x = np.array([777_777], dtype=np.int64)
        ad.feed_download_rows(self._rows(x, b0[:1], rng))
        assert int(ad._id_table[777_777]) == -1  # dropped & memoized
        t["now"] = 30.0  # the original hosts all expire
        ad.feed_download_rows(self._rows(x, b0[:1], rng))
        assert int(ad._id_table[777_777]) >= 0
        assert ad.evicted_nodes > 0

    def test_returning_host_in_eviction_chunk_is_touched_not_evicted(self):
        """A long-silent host appearing in the SAME chunk as the new
        host that triggers eviction is alive right now: it keeps its id,
        its edges train, and its embedding row survives."""
        cluster = _mk_cluster()
        tr = _mk_trainer(cluster, node_ttl=10.0, native_ingest=False)
        ad = tr.make_wire_adapter()
        t = {"now": 0.0}
        ad.clock = lambda: t["now"]
        rng = np.random.default_rng(3)
        b0 = np.arange(N_NODES, dtype=np.int64) + 10_000
        ad.feed_download_rows(self._rows(b0, np.roll(b0, 1), rng))
        h_id = int(ad._id_table[10_000])
        t["now"] = 30.0  # everyone silent past ttl
        new = np.array([888_888], dtype=np.int64)
        before = ad.overflow_edges
        ad.feed_download_rows(self._rows(new, b0[:1], rng))
        assert int(ad._id_table[10_000]) == h_id, "live host lost its id"
        assert int(ad._id_table[888_888]) >= 0
        assert ad.overflow_edges == before, "live host's edge was dropped"
        tr.apply_pending_recycles()
        for leaf in self._embedding_leaves(tr.state.params):
            assert np.abs(leaf[h_id]).sum() > 0, "live host row reset"

    def test_adapter_mapping_survives_checkpoint_resume(self, tmp_path):
        """ttl-mode id mappings are clock-driven, hence non-replayable:
        they ride in the checkpoint so a restarted trainer keeps every
        host on the dense id whose embedding learned it."""
        cluster = _mk_cluster()
        tr = _mk_trainer(cluster, tmp_path, node_ttl=10.0, native_ingest=False)
        ad = tr.make_wire_adapter()
        t = {"now": 1000.0}
        ad.clock = lambda: t["now"]
        rng = np.random.default_rng(4)
        b0 = np.arange(N_NODES, dtype=np.int64) + 10_000
        ad.feed_download_rows(self._rows(b0, np.roll(b0, 1), rng))
        mapping = ad._id_table[b0].copy()
        feat_cnt = ad._feat_cnt.copy()
        tr.checkpoint()

        tr2 = _mk_trainer(cluster, tmp_path, node_ttl=10.0, native_ingest=False)
        assert tr2.resume()
        ad2 = tr2.make_wire_adapter()
        ad2.clock = lambda: t["now"] + 1.0
        np.testing.assert_array_equal(ad2._id_table[b0], mapping)
        assert ad2._next_id == N_NODES
        np.testing.assert_array_equal(ad2._feat_cnt, feat_cnt)
        # Hosts keep their ids on their next appearance after restart.
        ad2.feed_download_rows(self._rows(b0[:4], b0[4:8], rng))
        np.testing.assert_array_equal(ad2._id_table[b0], mapping)
