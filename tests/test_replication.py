"""Manager HA: replicated StateBackend, lease/fencing, hot standby,
and transparent client failover (DESIGN.md §20; ISSUE 9).

In-process coverage of the replication subsystem; the cross-process
leader-SIGKILL-with-standby drill lives in tests/test_manager_recovery.py.
"""

from __future__ import annotations

import json
import logging
import random
import urllib.error
import urllib.request

import numpy as np
import pytest

from dragonfly2_tpu.manager.cluster import ClusterManager
from dragonfly2_tpu.manager.registry import KVBlobStore, ModelRegistry
from dragonfly2_tpu.manager.replication import (
    REPLICATION_AUTH_HEADER,
    LogFollower,
    NotLeaderError,
    ReplicatedStateBackend,
    StaleTermError,
    sign_lease,
    sign_replication_request,
    verify_lease,
)
from dragonfly2_tpu.manager.rest import ManagerRESTServer
from dragonfly2_tpu.manager.state import MemoryBackend, SQLiteBackend
from dragonfly2_tpu.rpc.resolver import ManagerEndpoints
from dragonfly2_tpu.rpc.retry import CircuitBreaker, DecorrelatedJitterBackoff
from dragonfly2_tpu.utils import faultinject


def _leader(clock, **kw):
    kw.setdefault("node_id", "L")
    kw.setdefault("lease_ttl_s", 10.0)
    return ReplicatedStateBackend(
        MemoryBackend(), role="leader", clock=clock, **kw
    )


def _standby(clock, **kw):
    kw.setdefault("node_id", "F")
    kw.setdefault("lease_ttl_s", 10.0)
    return ReplicatedStateBackend(
        MemoryBackend(), role="standby", clock=clock, **kw
    )


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# Op log + leader commit path
# ---------------------------------------------------------------------------


class TestOpLog:
    def test_every_write_appends_term_seq_before_commit(self):
        clock = _Clock()
        b = _leader(clock)
        t = b.table("models")
        t.put("m1", {"id": "m1"})
        t.put_many({"m2": {"id": "m2"}, "m3": {"id": "m3"}})
        t.delete("m3")
        entries = b.log.entries_since(0)
        assert [(e["seq"], e["term"], e["op"]) for e in entries] == [
            (1, 1, "put_many"), (2, 1, "put_many"), (3, 1, "delete"),
        ]
        assert all(e["ns"] == "models" for e in entries)
        assert b.table("models").load_all() == {
            "m1": {"id": "m1"}, "m2": {"id": "m2"},
        }

    def test_failed_data_commit_discards_the_log_entry(self, tmp_path):
        """The crash witness for a fault injected between the WAL
        append and the data commit: the caller is TOLD the write failed,
        so the appended entry must not survive — it would ship to
        followers (and replay at boot) as a write the leader's own table
        never took, and the next successful commit would advance the
        applied watermark past it, making the divergence permanent."""
        db = str(tmp_path / "s.db")
        b = ReplicatedStateBackend(SQLiteBackend(db), node_id="L")
        b.table("models").put("m1", {"id": "m1"})
        # Drop exactly the DATA commit (the models-namespace put); the
        # log append (replication_log namespace) has already landed.
        inj = faultinject.FaultInjector([
            faultinject.FaultSpec(site="state.put.models", kind="drop", at=(0,)),
        ])
        with faultinject.installed(inj):
            with pytest.raises(ConnectionError):
                b.table("models").put("m2", {"id": "m2"})
        # The failed write's entry is gone: nothing ships, and a later
        # commit (seq 3 > failed seq 2) must not strand a divergence.
        assert [e["seq"] for e in b.log.entries_since(0)] == [1]
        b.table("models").put("m3", {"id": "m3"})
        follower = _standby(_Clock())
        follower.apply_ops(b.log.entries_since(0))
        assert follower.table("models").load_all() == {
            "m1": {"id": "m1"}, "m3": {"id": "m3"},
        }
        b.close()

        b2 = ReplicatedStateBackend(SQLiteBackend(db), node_id="L")
        assert b2.table("models").load_all() == {
            "m1": {"id": "m1"}, "m3": {"id": "m3"},
        }, "boot replay must not resurrect a write the caller saw fail"
        b2.close()

    def test_crash_between_append_and_commit_replays_at_boot(self, tmp_path):
        """The write-ahead contract: a genuine CRASH (process death
        after the log append, before the data commit — the caller never
        got an answer) converges by idempotent replay at boot."""
        db = str(tmp_path / "s.db")
        b = ReplicatedStateBackend(SQLiteBackend(db), node_id="L")
        b.table("models").put("m1", {"id": "m1"})
        # Simulate the torn stop: the log row is durably appended but
        # the process dies before fn() runs (no discard, no data row).
        b.log.append({
            "term": b.term, "ns": "models", "op": "put_many",
            "items": {"m2": {"id": "m2"}},
        })
        assert b.log.seq == 2
        b.close()

        b2 = ReplicatedStateBackend(SQLiteBackend(db), node_id="L")
        assert b2.table("models").load_all() == {
            "m1": {"id": "m1"}, "m2": {"id": "m2"},
        }, "boot replay must apply the logged-but-uncommitted tail"
        b2.close()

    def test_log_survives_restart_and_seq_continues(self, tmp_path):
        db = str(tmp_path / "s.db")
        b = ReplicatedStateBackend(SQLiteBackend(db), node_id="L")
        b.table("crud").put("a", {"v": 1})
        b.close()
        b2 = ReplicatedStateBackend(SQLiteBackend(db), node_id="L")
        b2.table("crud").put("b", {"v": 2})
        assert [e["seq"] for e in b2.log.entries_since(0)] == [1, 2]
        b2.close()


# ---------------------------------------------------------------------------
# Follower application, snapshot bootstrap, lag
# ---------------------------------------------------------------------------


class TestFollowerApply:
    def test_snapshot_then_incremental_tail(self):
        clock = _Clock()
        leader = _leader(clock)
        t = leader.table("models")
        t.put("m1", {"id": "m1"})
        t.put("gone", {"id": "gone"})
        t.delete("gone")

        follower = _standby(clock)
        # Standby boot-time rows (e.g. ensure_default_cluster analog)
        # that the leader deleted/never had must not survive the sync.
        with follower.applying():
            follower.table("models").put("stale", {"id": "stale"})
        follower.apply_snapshot(leader.snapshot())
        assert follower.table("models").load_all() == {"m1": {"id": "m1"}}
        assert follower.log.applied == leader.log.seq

        t.put("m2", {"id": "m2"})
        touched = follower.apply_ops(
            leader.log.entries_since(follower.log.applied)
        )
        assert touched == {"models"}
        assert follower.table("models").get("m2") == {"id": "m2"}

    def test_apply_is_idempotent_and_skips_applied_seqs(self):
        clock = _Clock()
        leader = _leader(clock)
        leader.table("models").put("m1", {"id": "m1"})
        follower = _standby(clock)
        entries = leader.log.entries_since(0)
        follower.apply_ops(entries)
        follower.apply_ops(entries)  # duplicate delivery
        assert follower.log.applied == 1
        assert follower.table("models").load_all() == {"m1": {"id": "m1"}}

    def test_replication_namespaces_never_ship_in_snapshots(self):
        clock = _Clock()
        leader = _leader(clock)
        leader.table("models").put("m1", {"id": "m1"})
        snap = leader.snapshot()
        assert "replication_log" not in snap["namespaces"]
        assert "replication_meta" not in snap["namespaces"]


# ---------------------------------------------------------------------------
# Lease, fencing, split brain
# ---------------------------------------------------------------------------


class TestLeaseAndFencing:
    def test_lease_signature_authenticates_leader_and_term(self):
        sig = sign_lease("secret", "L", 3)
        lease = {"leader_id": "L", "term": 3, "sig": sig}
        assert verify_lease("secret", lease)
        assert not verify_lease("other-secret", lease)
        assert not verify_lease("secret", dict(lease, term=4))
        assert not verify_lease("secret", dict(lease, leader_id="evil"))

    def test_standby_rejects_writes(self):
        follower = _standby(_Clock())
        with pytest.raises(NotLeaderError):
            follower.table("models").put("x", {})

    def test_expired_lease_fences_the_leader(self):
        clock = _Clock()
        leader = _leader(clock, lease_ttl_s=5.0)
        leader.table("models").put("m1", {"id": "m1"})
        clock.t = 4.0  # inside the TTL: renewal extends
        leader.renew_lease()
        clock.t = 8.0
        leader.table("models").put("m2", {"id": "m2"})
        clock.t = 10.0  # past expiry, no renewal
        with pytest.raises(NotLeaderError):
            leader.table("models").put("m3", {"id": "m3"})

    def test_renewing_an_expired_lease_steps_down_not_resurrects(self):
        """The split-brain fix: a paused/partitioned leader's LeaseKeeper
        must NOT re-extend a lease that already lapsed — past expiry a
        standby may hold term+1 and nothing pushes that term back here
        (followers pull).  Renewal past expiry demotes permanently."""
        clock = _Clock()
        leader = _leader(clock, lease_ttl_s=5.0)
        leader.table("models").put("m1", {"id": "m1"})
        clock.t = 20.0  # the pause: lease long dead
        with pytest.raises(NotLeaderError):
            leader.renew_lease()
        assert leader.role == "standby"
        with pytest.raises(NotLeaderError):
            leader.table("models").put("z", {"id": "z"})
        # No resurrection path: renewing again still refuses.
        with pytest.raises(NotLeaderError):
            leader.renew_lease()

    def test_restarted_leader_defers_to_peer_with_higher_term(self):
        """A restarted fenced leader (role=leader in its config) probes
        ha.peers at boot and joins as a standby when a successor holds a
        higher term."""
        from dragonfly2_tpu.manager.replication import probe_peer_term

        clock = _Clock()
        successor = _standby(clock, lease_ttl_s=30.0)
        successor.promote()  # term 2
        rest = _rest_for(successor)
        try:
            term, url = probe_peer_term([rest.url, "http://127.0.0.1:9"])
            assert (term, url) == (2, rest.url)
            old = _leader(clock, lease_ttl_s=30.0)  # reboots at term 1
            if term > old.term:
                old.observe_term(term)
            assert old.role == "standby"
            with pytest.raises(NotLeaderError):
                old.table("models").put("z", {"id": "z"})
        finally:
            rest.stop()

    def test_split_brain_old_leader_post_lease_write_rejected_by_term(self):
        """The acceptance split-brain fence: leader pauses past its
        lease, follower promotes with term+1 — the zombie can neither
        commit locally (lease) nor ship its history (term)."""
        clock = _Clock()
        leader = _leader(clock, lease_ttl_s=5.0)
        leader.table("models").put("m1", {"id": "m1"})
        follower = _standby(clock, lease_ttl_s=5.0)
        follower.apply_snapshot(leader.snapshot())

        clock.t = 10.0  # leader paused past lease expiry
        follower.promote()
        assert follower.role == "leader" and follower.term == 2
        follower.table("models").put("f1", {"id": "f1"})

        # Zombie's own commit gate refuses...
        with pytest.raises(NotLeaderError):
            leader.table("models").put("z", {"id": "z"})
        # ...and even a hand-shipped term-1 op is rejected by term.
        zombie_op = {
            "seq": follower.log.seq + 1, "term": 1, "ns": "models",
            "op": "put_many", "items": {"z": {"id": "z"}},
        }
        with pytest.raises(StaleTermError):
            follower.apply_ops([zombie_op])
        assert follower.table("models").get("z") is None

        # The fenced leader observing the new term demotes permanently.
        leader.observe_term(follower.term)
        assert leader.role == "standby"
        with pytest.raises(NotLeaderError):
            leader.renew_lease()

    def test_promotion_is_counted_and_roles_exported(self):
        from dragonfly2_tpu.rpc.metrics import MANAGER_ROLE

        clock = _Clock()
        follower = _standby(clock)
        before_leader = MANAGER_ROLE.value(role="leader")
        follower.promote()
        assert follower.status()["failovers"] == 1
        assert MANAGER_ROLE.value(role="leader") == 1.0 >= before_leader


# ---------------------------------------------------------------------------
# LogFollower over the real REST surface
# ---------------------------------------------------------------------------


def _rest_for(backend, registry=None):
    server = ManagerRESTServer(
        registry if registry is not None else ModelRegistry(backend=backend),
        ClusterManager(),
        state_backend=backend,
        ha=backend,
    )
    server.serve()
    return server


class TestFollowerOverREST:
    def test_tail_apply_health_and_lag(self):
        clock = _Clock()
        leader = _leader(clock, lease_ttl_s=30.0)
        registry = ModelRegistry(KVBlobStore(leader), backend=leader)
        rest = _rest_for(leader, registry)
        follower_backend = _standby(clock, lease_ttl_s=30.0)
        follower = LogFollower(
            follower_backend, rest.url, clock=clock, poll_interval_s=0.05
        )
        try:
            registry.create_model(
                name="m", type="mlp", scheduler_id="s", artifact=b"\x01" * 8,
            )
            follower.poll_once()
            health = follower.health()
            assert health["applied_seq"] == health["leader_seq"] > 0
            assert health["lag_seconds"] == 0.0
            assert not follower.promoted
            # The replicated registry row AND its blob row arrived.
            reloaded = ModelRegistry(
                KVBlobStore(follower_backend), backend=follower_backend
            )
            m = reloaded.list(scheduler_id="s", name="m")[0]
            assert reloaded.load_artifact(m) == b"\x01" * 8
        finally:
            rest.stop()

    def test_replication_routes_and_standby_503(self):
        clock = _Clock()
        leader = _leader(clock, lease_ttl_s=30.0)
        rest = _rest_for(leader)
        try:
            with urllib.request.urlopen(
                rest.url + "/api/v1/replication:status", timeout=5
            ) as r:
                status = json.loads(r.read())
            assert status["role"] == "leader"
            assert verify_lease(leader.lease_secret, status["lease"])
        finally:
            rest.stop()

        standby = _standby(clock)
        rest2 = _rest_for(standby)
        try:
            # Reads answer; writes 503 with Retry-After.
            with urllib.request.urlopen(
                rest2.url + "/api/v1/healthy", timeout=5
            ) as r:
                assert json.loads(r.read())["role"] == "standby"
            req = urllib.request.Request(
                rest2.url + "/api/v1/models",
                data=json.dumps({"name": "m"}).encode(),
                headers={"Content-Type": "application/json"}, method="POST",
            )
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(req, timeout=5)
            assert err.value.code == 503
            assert err.value.headers.get("Retry-After") == "1"
        finally:
            rest2.stop()

    def test_lease_expiry_takeover_over_the_wire(self):
        clock = _Clock()
        leader = _leader(clock, lease_ttl_s=4.0)
        rest = _rest_for(leader)
        follower_backend = _standby(clock, lease_ttl_s=4.0)
        promoted = []
        follower = LogFollower(
            follower_backend, rest.url, clock=clock,
            on_promote=lambda: promoted.append(True),
        )
        try:
            leader.table("models").put("m1", {"id": "m1"})
            follower.poll_once()  # fresh lease observed
            assert follower.health()["lease_remaining_s"] > 0
        finally:
            rest.stop()  # the leader dies
        # Lease still fresh: no premature takeover.
        follower.poll_once()
        assert not follower.promoted
        # Lease ages out (+ grace) with the leader unreachable → promote.
        clock.t = 20.0
        follower.poll_once()
        assert follower.promoted and promoted == [True]
        assert follower_backend.role == "leader"
        assert follower_backend.term == 2
        assert follower_backend.table("models").get("m1") == {"id": "m1"}
        follower_backend.table("models").put("m2", {"id": "m2"})


# ---------------------------------------------------------------------------
# Replication-fetch auth: the data routes demand the shared secret
# ---------------------------------------------------------------------------


class TestReplicationAuth:
    def test_log_and_snapshot_refuse_unauthenticated_fetches(self):
        """The :log/:snapshot routes carry every namespace — users/PATs
        credential rows included on default deployments — so a fetch
        without proof of the lease_secret must 403, not dump state."""
        clock = _Clock()
        leader = _leader(clock, lease_ttl_s=30.0)
        leader.table("users").put("root", {"password_hash": "h", "salt": "s"})
        rest = _rest_for(leader)
        try:
            for route in ("replication:snapshot", "replication:log"):
                with pytest.raises(urllib.error.HTTPError) as err:
                    urllib.request.urlopen(
                        f"{rest.url}/api/v1/{route}", timeout=5
                    )
                assert err.value.code == 403
                # A token signed with the WRONG secret fails too.
                req = urllib.request.Request(
                    f"{rest.url}/api/v1/{route}",
                    headers={REPLICATION_AUTH_HEADER: sign_replication_request(
                        "not-the-secret", f"/api/v1/{route}"
                    )},
                )
                with pytest.raises(urllib.error.HTTPError) as err:
                    urllib.request.urlopen(req, timeout=5)
                assert err.value.code == 403
        finally:
            rest.stop()

    def test_secret_holder_fetches_and_follower_sends_the_header(self):
        clock = _Clock()
        leader = _leader(clock, lease_ttl_s=30.0)
        leader.table("crud").put("m1", {"id": "m1"})
        rest = _rest_for(leader)
        try:
            path = "/api/v1/replication:log"
            req = urllib.request.Request(
                rest.url + path + "?from_seq=0",
                headers={REPLICATION_AUTH_HEADER: sign_replication_request(
                    leader.lease_secret, path
                )},
            )
            with urllib.request.urlopen(req, timeout=5) as r:
                payload = json.loads(r.read())
            assert [e["seq"] for e in payload["entries"]] == [1]
            # The LogFollower authenticates transparently (shared secret).
            follower = _standby(clock, lease_ttl_s=30.0)
            LogFollower(follower, rest.url, clock=clock).poll_once()
            assert follower.table("crud").get("m1") == {"id": "m1"}
        finally:
            rest.stop()

    def test_ha_config_refuses_the_default_lease_secret(self):
        from dragonfly2_tpu.config.schema import (
            DEFAULT_LEASE_SECRET,
            ConfigError,
            HASection,
        )

        # The schema default and the backend constructor default are the
        # same placeholder (kept in sync by hand across the layers).
        import inspect

        sig = inspect.signature(ReplicatedStateBackend.__init__)
        assert sig.parameters["lease_secret"].default == DEFAULT_LEASE_SECRET

        HASection().validate()  # HA off: the placeholder is fine
        with pytest.raises(ConfigError):
            HASection(enable=True).validate()
        with pytest.raises(ConfigError):
            HASection(replicate_from="http://leader:1").validate()
        with pytest.raises(ConfigError):
            HASection(enable=True, lease_secret="short").validate()
        HASection(enable=True, lease_secret="x" * 16).validate()


# ---------------------------------------------------------------------------
# Log compaction: bounded growth, snapshot fallback past the floor
# ---------------------------------------------------------------------------


class TestLogCompaction:
    def test_leader_truncates_below_the_retention_window(self):
        clock = _Clock()
        leader = _leader(clock, lease_ttl_s=3600.0)
        leader.COMPACT_EVERY = 8
        leader.RETAIN_OPS = 8
        t = leader.table("models")
        for i in range(40):
            t.put(f"m{i}", {"id": f"m{i}"})
        entries = leader.log.entries_since(0)
        assert len(entries) <= 8 + leader.COMPACT_EVERY
        assert leader.log.floor > 1
        assert entries[0]["seq"] == leader.log.floor
        # Data state is complete regardless of what the log retains.
        assert len(t.load_all()) == 40

    def test_truncation_never_eats_the_unapplied_tail(self, tmp_path):
        db = str(tmp_path / "s.db")
        b = ReplicatedStateBackend(SQLiteBackend(db), node_id="L")
        b.table("models").put("m1", {"id": "m1"})
        # A crash-pending entry (appended, data commit never ran)...
        b.log.append({
            "term": b.term, "ns": "models", "op": "put_many",
            "items": {"m2": {"id": "m2"}},
        })
        # ...survives any truncation request, however aggressive.
        b.log.truncate_below(10_000)
        assert [e["seq"] for e in b.log.pending()] == [2]
        b.close()
        b2 = ReplicatedStateBackend(SQLiteBackend(db), node_id="L")
        assert b2.table("models").get("m2") == {"id": "m2"}
        b2.close()

    def test_sqlite_range_scan_matches_the_base_filter(self, tmp_path):
        sql = SQLiteBackend(str(tmp_path / "s.db")).table("ns")
        mem = MemoryBackend().table("ns")
        for t in (sql, mem):
            for i in range(10):
                t.put(f"{i:020d}", {"i": i})
        assert sql.load_range(f"{4:020d}") == mem.load_range(f"{4:020d}")
        sql.delete_range(f"{3:020d}")
        mem.delete_range(f"{3:020d}")
        assert sql.load_all() == mem.load_all()
        assert sorted(sql.load_all()) == [f"{i:020d}" for i in range(3, 10)]

    def test_follower_behind_the_floor_rebootstraps_via_snapshot(self):
        clock = _Clock()
        leader = _leader(clock, lease_ttl_s=3600.0)
        leader.COMPACT_EVERY = 4
        leader.RETAIN_OPS = 4
        rest = _rest_for(leader)
        follower_backend = _standby(clock, lease_ttl_s=3600.0)
        follower = LogFollower(follower_backend, rest.url, clock=clock)
        try:
            leader.table("crud").put("m0", {"id": "m0"})
            follower.poll_once()  # bootstrapped + caught up
            assert follower_backend.log.applied == leader.log.seq
            # The leader races far ahead; compaction truncates past the
            # follower's watermark.
            for i in range(1, 30):
                leader.table("crud").put(f"m{i}", {"id": f"m{i}"})
            assert leader.log.floor > follower_backend.log.applied + 1
            follower.poll_once()
            assert follower_backend.log.applied == leader.log.seq
            assert len(follower_backend.table("crud").load_all()) == 30
            assert follower.lag_seconds() == 0.0
        finally:
            rest.stop()


# ---------------------------------------------------------------------------
# Client failover: ManagerEndpoints
# ---------------------------------------------------------------------------


def _http_503():
    import io

    return urllib.error.HTTPError(
        "http://x", 503, "standby", {}, io.BytesIO(b"{}")
    )


class TestManagerEndpoints:
    def test_parses_comma_spec_and_is_sticky(self):
        eps = ManagerEndpoints("http://a:1, http://b:2")
        assert eps.all() == ["http://a:1", "http://b:2"]
        calls = []

        def fn(base):
            calls.append(base)
            if base == "http://a:1":
                raise ConnectionError("down")
            return "ok"

        assert eps.call(fn) == "ok"
        assert calls == ["http://a:1", "http://b:2"]
        # Sticky: the next call goes straight to the survivor.
        assert eps.call(fn) == "ok"
        assert calls[-1] == "http://b:2"

    def test_503_fails_over_but_404_propagates(self):
        eps = ManagerEndpoints(["http://a:1", "http://b:2"])
        seen = []

        def standby_then_ok(base):
            seen.append(base)
            if base == "http://a:1":
                raise _http_503()
            return "leader"

        assert eps.call(standby_then_ok) == "leader"
        assert seen == ["http://a:1", "http://b:2"]

        import io

        def not_found(base):
            raise urllib.error.HTTPError(
                base, 404, "nope", {}, io.BytesIO(b"{}")
            )

        with pytest.raises(urllib.error.HTTPError):
            eps.call(not_found)

    def test_all_down_raises_last_error_and_counts_failovers(self):
        from dragonfly2_tpu.rpc.metrics import (
            MANAGER_ENDPOINT_FAILOVERS_TOTAL,
        )

        eps = ManagerEndpoints("http://a:1,http://b:2", client="t-all-down")
        before = MANAGER_ENDPOINT_FAILOVERS_TOTAL.value(client="t-all-down")

        def dead(base):
            raise ConnectionError(base)

        with pytest.raises(ConnectionError):
            eps.call(dead)
        after = MANAGER_ENDPOINT_FAILOVERS_TOTAL.value(client="t-all-down")
        assert after == before + 2  # one rotation per dead endpoint

    def test_shared_instance_moves_every_client(self):
        """The cli/scheduler wiring claim: one resolver instance shared
        by two clients — the first failover moves both."""
        from dragonfly2_tpu.jobs.remote import RemoteJobClient
        from dragonfly2_tpu.rollout.client import RolloutRESTClient

        eps = ManagerEndpoints("http://a:1,http://b:2")
        jobs = RemoteJobClient(eps)
        rollout = RolloutRESTClient(eps)
        assert jobs.endpoints is rollout.endpoints is eps
        eps.failover("http://a:1")
        assert jobs.base == rollout.base_url == "http://b:2"


# ---------------------------------------------------------------------------
# Jittered backoff (satellite): spread + reproducibility
# ---------------------------------------------------------------------------


class TestDecorrelatedJitterBackoff:
    def test_seeded_schedule_is_reproducible(self):
        a = DecorrelatedJitterBackoff(base=1.0, cap=30.0, rng=random.Random(7))
        b = DecorrelatedJitterBackoff(base=1.0, cap=30.0, rng=random.Random(7))
        assert [a.next() for _ in range(8)] == [b.next() for _ in range(8)]

    def test_spread_grows_decorrelated_and_capped(self):
        bo = DecorrelatedJitterBackoff(base=1.0, cap=10.0, rng=random.Random(3))
        seq = [bo.next() for _ in range(64)]
        assert all(1.0 <= v <= 10.0 for v in seq)
        assert len({round(v, 6) for v in seq}) > 32, "no spread = herd"
        # Two differently-seeded fleets do NOT synchronize.
        other = DecorrelatedJitterBackoff(
            base=1.0, cap=10.0, rng=random.Random(4)
        )
        assert [other.next() for _ in range(8)] != seq[:8]

    def test_reset_returns_to_base_envelope(self):
        bo = DecorrelatedJitterBackoff(base=1.0, cap=60.0, rng=random.Random(5))
        for _ in range(10):
            bo.next()
        bo.reset()
        assert bo.next() <= 3.0  # uniform(base, base*3)

    def test_cluster_client_and_dynconfig_take_seeded_rngs(self):
        from dragonfly2_tpu.manager.dynconfig import Dynconfig
        from dragonfly2_tpu.rpc.cluster_client import RemoteClusterClient

        c1 = RemoteClusterClient(
            "http://m:1", backoff_rng=random.Random(11),
            keepalive_interval_s=20.0,
        )
        c2 = RemoteClusterClient(
            "http://m:1", backoff_rng=random.Random(11),
            keepalive_interval_s=20.0,
        )
        assert [c1._backoff.next() for _ in range(5)] == [
            c2._backoff.next() for _ in range(5)
        ]

        def failing():
            raise ConnectionError("manager down")

        d1 = Dynconfig(failing, refresh_interval=60.0,
                       backoff_rng=random.Random(12))
        d2 = Dynconfig(failing, refresh_interval=60.0,
                       backoff_rng=random.Random(12))
        assert d1.refresh() is False and d1.last_refresh_ok is False
        assert d2.refresh() is False
        assert [d1._backoff.next() for _ in range(5)] == [
            d2._backoff.next() for _ in range(5)
        ]


# ---------------------------------------------------------------------------
# SQLite hardening (satellite)
# ---------------------------------------------------------------------------


class TestSQLiteHardening:
    def test_busy_timeout_and_wal_set_at_open(self, tmp_path):
        b = SQLiteBackend(str(tmp_path / "s.db"))
        assert b._conn.execute("PRAGMA busy_timeout").fetchone()[0] == 5000
        assert (
            b._conn.execute("PRAGMA journal_mode").fetchone()[0].lower()
            == "wal"
        )
        b.close()

    def test_close_is_idempotent(self, tmp_path):
        b = SQLiteBackend(str(tmp_path / "s.db"))
        b.close()
        b.close()  # second close: no "closed database" explosion

    def test_migration_commits_all_namespaces_in_one_transaction(
        self, tmp_path
    ):
        """Crash mid-migration → NOTHING imported (the idempotency check
        re-imports next boot); never a half-migrated backend."""
        import sqlite3

        from dragonfly2_tpu.manager.state import migrate_legacy_sqlite

        models_db = str(tmp_path / "manager.db")
        conn = sqlite3.connect(models_db)
        conn.execute(
            "CREATE TABLE models (id TEXT PRIMARY KEY, name TEXT, type TEXT,"
            " version INTEGER, scheduler_id TEXT, state TEXT, evaluation "
            "TEXT, blob_key TEXT, created_at REAL, updated_at REAL)"
        )
        conn.execute(
            "INSERT INTO models VALUES ('m1','r','mlp',1,'s','active',"
            "'{}','b',1.0,2.0)"
        )
        conn.commit(); conn.close()
        crud_db = str(tmp_path / "crud.db")
        conn = sqlite3.connect(crud_db)
        conn.execute(
            "CREATE TABLE crud_rows (kind TEXT, id TEXT, value TEXT, "
            "PRIMARY KEY (kind, id))"
        )
        conn.execute(
            "INSERT INTO crud_rows VALUES ('application','a1','{\"id\": "
            "\"a1\"}')"
        )
        conn.commit(); conn.close()

        backend = SQLiteBackend(str(tmp_path / "state.db"))
        # Drop at the second namespace's seam: with per-namespace
        # transactions this would leave models imported and crud not.
        inj = faultinject.FaultInjector([
            faultinject.FaultSpec(site="state.put.crud", kind="drop", at=(0,)),
        ])
        with faultinject.installed(inj):
            with pytest.raises(ConnectionError):
                migrate_legacy_sqlite(
                    backend, models_db=models_db, crud_db=crud_db
                )
        assert backend.table("models").load_all() == {}, (
            "partial migration committed — the one-transaction contract "
            "is torn"
        )
        assert backend.table("crud").load_all() == {}
        # Next boot: full import succeeds and is idempotent.
        counts = migrate_legacy_sqlite(
            backend, models_db=models_db, crud_db=crud_db
        )
        assert counts == {"models": 1, "crud": 1}
        assert migrate_legacy_sqlite(
            backend, models_db=models_db, crud_db=crud_db
        ) == {}
        backend.close()


# ---------------------------------------------------------------------------
# Circuit-breaker visibility (satellite)
# ---------------------------------------------------------------------------


class TestBreakerVisibility:
    def test_state_gauge_tracks_transitions(self):
        from dragonfly2_tpu.rpc.metrics import CIRCUIT_BREAKER_STATE

        clock = _Clock()
        br = CircuitBreaker(
            failure_threshold=2, reset_timeout_s=1.0, clock=clock,
            name="parent-9",
        )
        assert CIRCUIT_BREAKER_STATE.value(target="parent-9") == 0.0
        br.record_failure()
        br.record_failure()
        assert CIRCUIT_BREAKER_STATE.value(target="parent-9") == 2.0
        clock.t = 2.0
        assert br.allow()  # open -> half_open probe
        assert CIRCUIT_BREAKER_STATE.value(target="parent-9") == 1.0
        br.record_success()
        assert CIRCUIT_BREAKER_STATE.value(target="parent-9") == 0.0

    def test_transitions_log_once_not_per_call(self, caplog):
        clock = _Clock()
        br = CircuitBreaker(
            failure_threshold=1, reset_timeout_s=10.0, clock=clock,
            name="parent-log",
        )
        with caplog.at_level(logging.INFO, logger="dragonfly2_tpu.rpc.retry"):
            br.record_failure()          # closed -> open: ONE warning
            for _ in range(50):
                br.record_failure()      # still open: silent
                br.allow()               # still open: silent
        opens = [
            r for r in caplog.records if "parent-log" in r.getMessage()
        ]
        assert len(opens) == 1 and opens[0].levelno == logging.WARNING

    def test_unnamed_breaker_stays_silent(self, caplog):
        br = CircuitBreaker(failure_threshold=1)
        with caplog.at_level(logging.INFO, logger="dragonfly2_tpu.rpc.retry"):
            br.record_failure()
        assert not caplog.records


# ---------------------------------------------------------------------------
# Metrics schema (satellite)
# ---------------------------------------------------------------------------


class TestMetricsSchema:
    def test_ha_metric_names_and_labels(self):
        from dragonfly2_tpu.rpc import metrics as m

        assert m.MANAGER_ROLE.name == "manager_role"
        assert m.MANAGER_ROLE.label_names == ("role",)
        assert m.REPLICATION_LAG.name == "manager_replication_lag_seconds"
        assert m.REPLICATION_LAG.label_names == ()
        assert m.MANAGER_FAILOVERS_TOTAL.name == "manager_failovers_total"
        assert m.MANAGER_FAILOVERS_TOTAL.label_names == ("node",)
        assert (
            m.MANAGER_ENDPOINT_FAILOVERS_TOTAL.name
            == "manager_endpoint_failovers_total"
        )
        assert m.MANAGER_ENDPOINT_FAILOVERS_TOTAL.label_names == ("client",)
        assert m.CIRCUIT_BREAKER_STATE.name == "rpc_circuit_breaker_state"
        assert m.CIRCUIT_BREAKER_STATE.label_names == ("target",)

    def test_exposition_renders_the_ha_plane(self):
        from dragonfly2_tpu.rpc import metrics as m
        from dragonfly2_tpu.utils.metrics import default_registry

        m.MANAGER_ROLE.set(1.0, role="leader")
        m.REPLICATION_LAG.set(0.25)
        m.MANAGER_FAILOVERS_TOTAL.inc(node="mgr-test")
        text = default_registry.expose_text()
        assert 'manager_role{role="leader"} 1.0' in text
        assert "manager_replication_lag_seconds 0.25" in text
        assert 'manager_failovers_total{node="mgr-test"}' in text


# ---------------------------------------------------------------------------
# Zero-pinning subscriber failover (in-process half of the drill)
# ---------------------------------------------------------------------------


class TestSubscriberFailover:
    def test_model_poll_fails_over_with_zero_pinning(self):
        """Leader dies, standby serves reads: the subscriber's poll
        sweeps the endpoint list inside the client and NEVER engages the
        PR-4 pin."""
        from dragonfly2_tpu.records.features import DOWNLOAD_FEATURE_DIM
        from dragonfly2_tpu.rpc.registry_client import RemoteRegistry
        from dragonfly2_tpu.scheduler import MLEvaluator, ModelSubscriber
        from dragonfly2_tpu.trainer.export import MLPScorer, scorer_to_bytes

        rng = np.random.default_rng(0)
        weights = [(
            rng.standard_normal(
                (DOWNLOAD_FEATURE_DIM, 1)
            ).astype(np.float32),
            np.zeros(1, dtype=np.float32),
        )]
        artifact = scorer_to_bytes(MLPScorer(weights=weights))

        clock = _Clock()
        leader = _leader(clock, lease_ttl_s=60.0)
        registry = ModelRegistry(KVBlobStore(leader), backend=leader)
        rest = _rest_for(leader, registry)
        model = registry.create_model(
            name="parent-bandwidth-mlp", type="mlp", scheduler_id="s1",
            artifact=artifact,
        )
        registry.activate(model.id)

        follower_backend = _standby(clock, lease_ttl_s=60.0)
        follower = LogFollower(follower_backend, rest.url, clock=clock)
        follower.poll_once()
        standby_registry = ModelRegistry(
            KVBlobStore(follower_backend), backend=follower_backend
        )
        standby_rest = ManagerRESTServer(
            standby_registry, ClusterManager(),
            state_backend=follower_backend, ha=follower_backend,
        )
        standby_rest.serve()

        remote = RemoteRegistry(f"{rest.url},{standby_rest.url}")
        subscriber = ModelSubscriber(
            remote, MLEvaluator(), scheduler_id="s1",
        )
        try:
            assert subscriber.refresh() is True  # served by the leader
            assert subscriber.pinned is False
            rest.stop()  # leader dies; standby keeps answering reads
            assert subscriber.refresh() is False  # same version, no swap
            assert subscriber.pinned is False, (
                "poll pinned despite a live standby — failover is broken"
            )
            assert remote.base_url == standby_rest.url
        finally:
            standby_rest.stop()
