"""Chaos e2e drills + fault-injection layer contract (ISSUE 1).

The reference proves resilience with e2e drills (test/e2e inside kind);
these are the failure-mode analogs against REAL processes and the real
wire, all driven by the deterministic fault layer (utils/faultinject +
sim/chaos):

- determinism: same scenario seed ⇒ byte-identical fault sequence;
- retry hardening: full jitter, per-attempt deadline propagation,
  circuit breaker give-up/half-open recovery;
- drill 1 — scheduler SIGKILLed mid-download: the late peer finishes
  through pex gossip fallback, digest verified;
- drill 2 — manager SIGKILLed: dynconfig's disk cache keeps the
  scheduler scheduling with the manager's cluster limits;
- drill 3 — daemon SIGKILLed mid-upload: its children reschedule onto
  the surviving parent, digest verified;
- drill 4 — trainer SIGKILLed mid-online-ingest (self-inflicted at a
  deterministic dispatch): orbax resume continues exactly-once — no
  duplicate, no lost records;
- truncation: injected torn piece bodies NEVER commit (length guard →
  refetch), digest verified;
- satellites: bench backend-init failure JSON, OAuth refresh race +
  HTTPError classification, job results that don't serialize.
"""

import json
import re
import threading
import time
import urllib.error
import urllib.request

import pytest

from dragonfly2_tpu.rpc.retry import (
    CircuitBreaker,
    CircuitOpenError,
    RetryBudgetExceeded,
    retry_call,
)
from dragonfly2_tpu.sim.chaos import (
    ChaosProcess,
    ChaosScenario,
    crash_at,
    drop_storm,
    free_port,
    replay_history,
    sha256_hex,
    wait_until,
)
from dragonfly2_tpu.utils import faultinject
from dragonfly2_tpu.utils.faultinject import FaultInjected, FaultSpec

PIECE = 64 * 1024


@pytest.fixture(autouse=True)
def _no_leaked_injector():
    yield
    faultinject.uninstall()


# ---------------------------------------------------------------------------
# Fault layer contract
# ---------------------------------------------------------------------------


class TestFaultLayerDeterminism:
    def _drive(self, inj):
        for _ in range(60):
            for site in ("rpc.client.register_peer", "piece.fetch",
                         "state.put.jobs"):
                try:
                    inj.fire(site)
                except Exception:  # noqa: BLE001 — injected
                    pass

    def test_same_seed_same_fault_sequence(self):
        sc = ChaosScenario(seed=7, faults=[
            FaultSpec(site="rpc.client.*", kind="drop", probability=0.3),
            FaultSpec(site="piece.*", kind="dferror", probability=0.2),
            FaultSpec(site="state.put.*", kind="drop", probability=0.1),
        ])
        h1 = replay_history(sc, self._drive)
        h2 = replay_history(sc, self._drive)
        assert h1 and h1 == h2
        h3 = replay_history(
            ChaosScenario(seed=8, faults=list(sc.faults)), self._drive
        )
        assert h3 != h1

    def test_explicit_indices_modulus_and_caps(self):
        inj = ChaosScenario(faults=[
            FaultSpec(site="a", kind="drop", at=(1, 3)),
            FaultSpec(site="b", kind="drop", every=2, max_fires=2),
        ]).injector()
        outcomes = []
        for _ in range(5):
            try:
                inj.fire("a")
                outcomes.append("ok")
            except FaultInjected:
                outcomes.append("drop")
        assert outcomes == ["ok", "drop", "ok", "drop", "ok"]
        dropped = 0
        for _ in range(8):
            try:
                inj.fire("b")
            except FaultInjected:
                dropped += 1
        assert dropped == 2  # every=2 would fire 4×; max_fires caps at 2

    def test_typed_dferror_and_truncate_and_env(self):
        from dragonfly2_tpu.utils.dferrors import Code, DfError, UnavailableError

        sc = ChaosScenario(seed=3, faults=[
            FaultSpec(site="rpc.*", kind="dferror", at=(0,), code=14),
            FaultSpec(site="rpc.*", kind="dferror", at=(1,),
                      code=int(Code.NOT_FOUND)),
            FaultSpec(site="*.body", kind="truncate", at=(0,), keep_bytes=2),
        ])
        inj = faultinject.install_from_env({faultinject.ENV_VAR: sc.to_json()})
        try:
            with pytest.raises(UnavailableError):
                inj.fire("rpc.client.x")
            with pytest.raises(DfError) as ei:
                inj.fire("rpc.client.x")
            assert ei.value.code is Code.NOT_FOUND
            assert inj.fire("piece.fetch.body", b"abcdef") == b"ab"
            assert inj.fire("piece.fetch.body", b"abcdef") == b"abcdef"
        finally:
            faultinject.uninstall()

    def test_crash_kind_uses_kill_hook(self):
        killed = []
        inj = faultinject.FaultInjector(
            [FaultSpec(site="trainer.dispatch", kind="crash", at=(2,))],
            kill=lambda: killed.append(True),
        )
        for _ in range(4):
            inj.fire("trainer.dispatch")
        assert killed == [True]
        assert [k[:3] for k in inj.history_keys()] == [
            ("trainer.dispatch", 2, "crash")
        ]

    def test_delay_uses_sleep_hook_and_uninstalled_is_noop(self):
        slept = []
        inj = faultinject.FaultInjector(
            [FaultSpec(site="s", kind="delay", at=(0,), delay_s=1.5)],
            sleep=slept.append,
        )
        inj.fire("s")
        assert slept == [1.5]
        # No injector installed: fire is a passthrough.
        assert faultinject.fire("anything", b"xy") == b"xy"


# ---------------------------------------------------------------------------
# Retry hardening (ISSUE acceptance: give-up, half-open, deadlines)
# ---------------------------------------------------------------------------


class TestRetryHardening:
    def test_gives_up_after_attempts_with_last_error(self):
        calls = []

        def dead():
            calls.append(1)
            raise ConnectionError("down")

        with pytest.raises(ConnectionError):
            retry_call(dead, attempts=4, sleep=lambda s: None)
        assert len(calls) == 4

    def test_full_jitter_bounded_by_exponential_envelope(self):
        import random

        delays = []

        def flaky():
            raise TimeoutError("t")

        with pytest.raises(TimeoutError):
            retry_call(
                flaky, attempts=5, base_delay=0.1, max_delay=0.6,
                sleep=delays.append, rng=random.Random(0),
            )
        assert len(delays) == 4
        for i, d in enumerate(delays):
            assert 0.0 <= d <= min(0.1 * 2**i, 0.6)
        # Deterministic with a seeded rng: replay gives the same schedule.
        delays2 = []
        with pytest.raises(TimeoutError):
            retry_call(
                flaky, attempts=5, base_delay=0.1, max_delay=0.6,
                sleep=delays2.append, rng=random.Random(0),
            )
        assert delays == delays2

    def test_budget_exceeded_raises_chained(self):
        clock = [0.0]

        def tick_sleep(s):
            clock[0] += s

        def dead():
            clock[0] += 0.4
            raise ConnectionError("down")

        with pytest.raises(RetryBudgetExceeded) as ei:
            retry_call(
                dead, attempts=50, base_delay=0.4, max_delay=0.4,
                deadline_s=1.0, sleep=tick_sleep, clock=lambda: clock[0],
            )
        assert isinstance(ei.value.__cause__, ConnectionError)

    def test_deadline_propagates_remaining_budget(self):
        clock = [0.0]
        seen = []

        def fn(deadline_s=None):
            seen.append(round(deadline_s, 6))
            clock[0] += 0.25
            raise TimeoutError("t")

        with pytest.raises((TimeoutError, RetryBudgetExceeded)):
            retry_call(
                fn, attempts=10, base_delay=0.0, deadline_s=1.0,
                sleep=lambda s: None, clock=lambda: clock[0],
            )
        # Each attempt saw the SHRINKING remainder, never the full budget
        # again — the transport can clamp its socket timeout to it.
        assert seen[0] == 1.0
        assert all(seen[i] > seen[i + 1] for i in range(len(seen) - 1))
        assert all(0 <= s <= 1.0 for s in seen)

    def test_breaker_opens_then_half_open_recovers(self):
        clock = [0.0]
        b = CircuitBreaker(
            failure_threshold=3, reset_timeout_s=5.0, clock=lambda: clock[0]
        )
        for _ in range(3):
            assert b.allow()
            b.record_failure()
        assert b.state == "open"
        # Open: fail fast, no call attempted.
        calls = []

        def fn():
            calls.append(1)
            return "ok"

        with pytest.raises(CircuitOpenError):
            retry_call(fn, attempts=3, sleep=lambda s: None, breaker=b)
        assert calls == []
        # Reset window passes → HALF-OPEN probe; success closes.
        clock[0] += 5.0
        assert retry_call(fn, attempts=1, breaker=b) == "ok"
        assert b.state == "closed" and calls == [1]

    def test_half_open_failure_reopens(self):
        clock = [0.0]
        b = CircuitBreaker(
            failure_threshold=1, reset_timeout_s=5.0, clock=lambda: clock[0]
        )
        b.record_failure()
        assert b.state == "open"
        clock[0] += 5.0
        assert b.allow()  # the probe
        b.record_failure()
        assert b.state == "open"  # single probe failure re-trips
        assert not b.allow()


# ---------------------------------------------------------------------------
# Truncation: no silent corruption (in-process swarm, injected torn body)
# ---------------------------------------------------------------------------


class TestTruncationNoSilentCorruption:
    def test_torn_piece_body_refetched_digest_intact(self, tmp_path):
        from dragonfly2_tpu.daemon import Daemon
        from dragonfly2_tpu.daemon.pex import GossipBus
        from dragonfly2_tpu.scheduler import (
            Evaluator,
            NetworkTopology,
            Resource,
            SchedulerService,
            Scheduling,
            SchedulingConfig,
        )
        from dragonfly2_tpu.scheduler.resource import Host

        resource = Resource()
        service = SchedulerService(
            resource,
            Scheduling(Evaluator(), SchedulingConfig(retry_interval=0)),
            None,
            NetworkTopology(resource.host_manager),
        )

        class Origin:
            def fetch(self, url, number, piece_size):
                return bytes((number + i) % 251 for i in range(PIECE))

        registry, bus = {}, GossipBus()
        daemons = []
        for i in range(3):
            h = Host(id=f"tr-host-{i}", hostname=f"tr{i}", ip=f"10.9.0.{i}",
                     port=8002, download_port=8001)
            h.stats.network.idc = "idc-a"
            resource.store_host(h)
            daemons.append(Daemon(
                h, service, storage_root=str(tmp_path / f"d{i}"),
                daemon_registry=registry, gossip_bus=bus,
                # The child (d2) has NO origin: it can only finish P2P.
                source_fetcher=Origin() if i < 2 else None,
                prefer_native=False,
            ))
        url = "https://origin/torn-blob"
        r0 = daemons[0].download(url, piece_size=PIECE, content_length=4 * PIECE)
        r1 = daemons[1].download(url, piece_size=PIECE, content_length=4 * PIECE)
        assert r0.ok and r1.ok
        want = sha256_hex(daemons[0].read_task_bytes(r0.task_id))

        # Child downloads P2P with the serving parent's upload body TORN
        # once on the first serve: the length guard must detect it, count
        # a failure, and refetch/reschedule — never commit a short body.
        scenario = ChaosScenario(faults=[
            FaultSpec(site="daemon.upload.body", kind="truncate",
                      at=(0,), keep_bytes=100),
        ])
        with faultinject.installed(scenario.injector()):
            r2 = daemons[2].download(
                url, piece_size=PIECE, content_length=4 * PIECE
            )
        assert r2.ok and not r2.back_to_source
        assert sha256_hex(daemons[2].read_task_bytes(r2.task_id)) == want
        assert r2.failed_pieces >= 1  # the torn body surfaced as a failure


# ---------------------------------------------------------------------------
# Drill 1 — scheduler SIGKILL mid-download → pex fallback, digest verified
# ---------------------------------------------------------------------------


class TestSchedulerKillDrill:
    def test_peer_finishes_via_pex_after_scheduler_sigkill(self, tmp_path):
        from dragonfly2_tpu.daemon import Daemon
        from dragonfly2_tpu.daemon.pex import GossipBus
        from dragonfly2_tpu.rpc import RemoteScheduler
        from dragonfly2_tpu.scheduler.resource import Host
        from dragonfly2_tpu.utils import idgen

        cfg = tmp_path / "sched.yaml"
        cfg.write_text(
            "server: {host: 127.0.0.1, port: 0, grpc_port: -1}\n"
            "scheduling: {retry_interval_s: 0.0}\n"
            f"storage: {{dir: {tmp_path / 'records'}, buffer_size: 1}}\n"
        )
        sched = ChaosProcess(
            ["-m", "dragonfly2_tpu.cli.scheduler", "--config", str(cfg)],
            ready_prefixes=["scheduler: serving"],
        ).start()
        try:
            line = sched.wait_ready(60)["scheduler: serving"]
            sched_url = re.search(r"rpc on (\S+)", line).group(1)

            class Origin:
                def fetch(self, url, number, piece_size):
                    return bytes((number * 7 + i) % 251 for i in range(PIECE))

            registry, bus = {}, GossipBus()

            def make_daemon(i, source):
                h = Host(id=f"ck-host-{i}", hostname=f"ck{i}",
                         ip=f"10.8.0.{i}", port=8002, download_port=8001)
                h.stats.network.idc = "idc-a"
                return Daemon(
                    h, RemoteScheduler(sched_url, timeout=2.0),
                    storage_root=str(tmp_path / f"ck{i}"),
                    daemon_registry=registry, gossip_bus=bus,
                    source_fetcher=source, prefer_native=False,
                )

            a = make_daemon(0, Origin())
            b = make_daemon(1, None)  # no origin: pex is its ONLY fallback

            url = "https://origin/chaos-blob"
            tid = idgen.task_id(url)
            r0 = a.download(url, piece_size=PIECE, content_length=4 * PIECE)
            assert r0.ok
            want = sha256_hex(a.read_task_bytes(tid))

            # B's download starts CONCURRENTLY; its first scheduler RPC
            # (announce, site index 0: the injector is installed after A's
            # download) is held by the injector until the scheduler has
            # been SIGKILLed: a mid-download control-plane death,
            # deterministic.
            scenario = ChaosScenario(faults=[
                FaultSpec(site="rpc.client.announce_host", kind="delay",
                          at=(0,)),
            ])
            in_announce, sched_dead = threading.Event(), threading.Event()

            def hold_until_scheduler_dead(_delay_s):
                in_announce.set()
                assert sched_dead.wait(60), "scheduler never killed"

            result = {}

            def download_b():
                try:
                    result["r"] = b.download(
                        url, piece_size=PIECE, content_length=4 * PIECE
                    )
                except BaseException as exc:  # noqa: BLE001 — asserted on below
                    result["raised"] = exc

            with faultinject.installed(
                scenario.injector(sleep=hold_until_scheduler_dead)
            ):
                t = threading.Thread(target=download_b)
                t.start()
                assert in_announce.wait(60), f"B never announced: {result}"
                sched.sigkill()
                assert sched.proc.returncode == -9
                sched_dead.set()
                t.join(timeout=60)
            assert not t.is_alive(), "download hung after scheduler kill"
            assert "raised" not in result, repr(result.get("raised"))
            r1 = result["r"]
            # Control plane dead → gossip-discovered holder served it.
            assert r1.ok, r1
            assert sha256_hex(b.read_task_bytes(tid)) == want
        finally:
            sched.stop()


# ---------------------------------------------------------------------------
# Drill 2 — manager SIGKILL → dynconfig disk fallback keeps scheduling
# ---------------------------------------------------------------------------


class TestManagerKillDrill:
    def test_dynconfig_disk_fallback_keeps_scheduling(self, tmp_path):
        from dragonfly2_tpu.manager.dynconfig import Dynconfig
        from dragonfly2_tpu.records.storage import Storage
        from dragonfly2_tpu.sim import SwarmConfig, SwarmSimulator

        port = free_port()
        cfg = tmp_path / "manager.yaml"
        cfg.write_text(
            f"server: {{host: 127.0.0.1, port: {port}, grpc_port: -1}}\n"
            f"registry: {{blob_dir: {tmp_path / 'mgr'}}}\n"
        )
        mgr = ChaosProcess(
            ["-m", "dragonfly2_tpu.cli.manager", "--config", str(cfg)],
            ready_prefixes=["manager: serving"],
        ).start()
        url = f"http://127.0.0.1:{port}"
        cache_path = str(tmp_path / "dynconfig-cache.json")

        def fetch():
            with urllib.request.urlopen(
                url + "/api/v1/clusters/c1:config", timeout=5
            ) as r:
                return json.loads(r.read())

        try:
            mgr.wait_ready(60)
            body = json.dumps({
                "id": "c1", "name": "c1",
                "scheduler_cluster_config": {"candidate_parent_limit": 2,
                                             "filter_parent_limit": 10},
            }).encode()
            req = urllib.request.Request(
                url + "/api/v1/clusters", data=body,
                headers={"Content-Type": "application/json"}, method="POST",
            )
            with urllib.request.urlopen(req, timeout=10):
                pass
            # A running client fetched once (writing the disk cache)...
            dyn0 = Dynconfig(fetch, cache_path=cache_path)
            assert dyn0.refresh() is True

            # ...then the manager dies.
            mgr.sigkill()
            with pytest.raises((urllib.error.URLError, ConnectionError)):
                fetch()

            # A RESTARTED scheduler's dynconfig (fresh instance, no
            # memory) has only the disk cache — which must still apply
            # the manager's cluster limits to live scheduling.
            sim = SwarmSimulator(
                Storage(str(tmp_path / "rec"), buffer_size=4),
                config=SwarmConfig(num_hosts=12, seed=3),
            )
            assert sim.scheduling.config.candidate_parent_limit == 4

            applied = []

            def observer(data):
                limit = data["scheduler_cluster_config"]["candidate_parent_limit"]
                sim.scheduling.config.candidate_parent_limit = limit
                applied.append(limit)

            dyn1 = Dynconfig(fetch, cache_path=cache_path)
            dyn1.register(observer)
            assert dyn1.refresh() is False  # fetch failed — disk fallback
            assert applied == [2]
            assert dyn1.get()["scheduler_cluster_config"][
                "candidate_parent_limit"] == 2

            # Scheduling CONTINUES under the cached config: a fresh child
            # gets parents, capped at the manager-set limit.
            url_task = "https://origin.example.com/mgr-drill"
            sim.seed_task(url_task, n_seeds=5)
            reg = sim.service.register_peer(host=sim.hosts[7], url=url_task)
            assert reg.schedule is not None and reg.schedule.parents
            assert 1 <= len(reg.schedule.parents) <= 2
        finally:
            mgr.stop()


# ---------------------------------------------------------------------------
# Drill 2b — manager leader dies WITH a standby attached → dynconfig
# fails over to the replica and never touches the disk fallback
# (Manager HA, DESIGN.md §20; the pin/fallback is the ALL-replicas-down
# last resort only)
# ---------------------------------------------------------------------------


class TestManagerFailoverDrill:
    def test_dynconfig_fails_over_to_standby_without_disk_fallback(
        self, tmp_path
    ):
        from dragonfly2_tpu.manager.cluster import ClusterManager
        from dragonfly2_tpu.manager.crud import CrudStore
        from dragonfly2_tpu.manager.dynconfig import Dynconfig
        from dragonfly2_tpu.manager.registry import ModelRegistry
        from dragonfly2_tpu.manager.replication import (
            LogFollower, ReplicatedStateBackend,
        )
        from dragonfly2_tpu.manager.rest import ManagerRESTServer
        from dragonfly2_tpu.manager.state import MemoryBackend
        from dragonfly2_tpu.rpc.resolver import ManagerEndpoints

        leader = ReplicatedStateBackend(
            MemoryBackend(), node_id="L", lease_ttl_s=60.0
        )
        crud = CrudStore(backend=leader)
        rest = ManagerRESTServer(
            ModelRegistry(backend=leader), ClusterManager(), crud=crud,
            state_backend=leader, ha=leader,
        )
        rest.serve()
        crud.create("cluster", id="c1", name="c1", scheduler_cluster_config={
            "candidate_parent_limit": 2, "filter_parent_limit": 10,
        })

        standby_backend = ReplicatedStateBackend(
            MemoryBackend(), node_id="F", role="standby", lease_ttl_s=60.0
        )
        follower = LogFollower(standby_backend, rest.url)
        follower.poll_once()
        standby_rest = ManagerRESTServer(
            ModelRegistry(backend=standby_backend), ClusterManager(),
            crud=CrudStore(backend=standby_backend),
            state_backend=standby_backend, ha=standby_backend,
        )
        standby_rest.serve()

        endpoints = ManagerEndpoints(f"{rest.url},{standby_rest.url}")
        cache_path = str(tmp_path / "dyn-cache.json")

        def fetch():
            def one(base):
                with urllib.request.urlopen(
                    base + "/api/v1/clusters/c1:config", timeout=5
                ) as r:
                    return json.loads(r.read())

            return endpoints.call(one)

        try:
            dyn = Dynconfig(fetch, cache_path=cache_path)
            assert dyn.refresh() is True
            # The leader dies; the standby replica holds the same rows.
            rest.stop()
            dyn2 = Dynconfig(fetch, cache_path=str(tmp_path / "absent.json"))
            assert dyn2.refresh() is True, (
                "fetch did not fail over to the standby"
            )
            assert dyn2.last_refresh_ok is True  # live fetch, NOT fallback
            assert dyn2.get()["scheduler_cluster_config"][
                "candidate_parent_limit"] == 2
            assert endpoints.current() == standby_rest.url
        finally:
            rest.stop()
            standby_rest.stop()


# ---------------------------------------------------------------------------
# Drill 3 — daemon SIGKILL mid-upload → children reschedule, digest verified
# ---------------------------------------------------------------------------


class TestDaemonKillMidUploadDrill:
    def test_children_reschedule_onto_surviving_parent(self, tmp_path):
        from dragonfly2_tpu.daemon import DaemonStorage
        from dragonfly2_tpu.daemon.conductor import Conductor
        from dragonfly2_tpu.records.storage import Storage
        from dragonfly2_tpu.rpc import HTTPPieceFetcher, RemoteScheduler
        from dragonfly2_tpu.rpc.daemon_control import (
            download_via_daemon,
            read_state,
        )
        from dragonfly2_tpu.rpc.scheduler_server import SchedulerHTTPServer
        from dragonfly2_tpu.scheduler import (
            Evaluator,
            NetworkTopology,
            Resource,
            SchedulerService,
            Scheduling,
            SchedulingConfig,
        )
        from dragonfly2_tpu.scheduler.resource import Host
        from dragonfly2_tpu.utils import idgen

        # Control plane IN-PROCESS (it must survive the daemon kill and
        # is where we watch rescheduling happen); parents are REAL
        # dfdaemon processes serving the piece plane over HTTP.
        resource = Resource()
        service = SchedulerService(
            resource,
            Scheduling(Evaluator(), SchedulingConfig(retry_interval=0)),
            Storage(str(tmp_path / "records"), buffer_size=4),
            NetworkTopology(resource.host_manager),
        )
        server = SchedulerHTTPServer(service)
        server.serve()

        blob = bytes(i % 249 for i in range(8 * PIECE))
        blob_path = tmp_path / "blob.bin"
        blob_path.write_bytes(blob)
        url = f"file://{blob_path}"
        tid = idgen.task_id(url)

        daemons = []
        try:
            for i in range(2):
                dcfg = tmp_path / f"daemon{i}.yaml"
                dcfg.write_text(
                    "server: {host: 127.0.0.1, port: 0, "
                    "advertise_ip: 127.0.0.1}\n"
                    f"storage: {{dir: {tmp_path / f'dstore{i}'}}}\n"
                    f"piece_size: {PIECE}\n"
                )
                d = ChaosProcess(
                    ["-m", "dragonfly2_tpu.cli.dfdaemon",
                     "--scheduler", server.url, "--config", str(dcfg)],
                    ready_prefixes=["dfdaemon: serving"],
                    env={**__import__("os").environ,
                         "DF_DAEMON_STATE": str(tmp_path / f"d{i}.json")},
                ).start()
                daemons.append(d)
            for i, d in enumerate(daemons):
                d.wait_ready(90)
                control = read_state(str(tmp_path / f"d{i}.json"))["url"]
                r = download_via_daemon(url, control)
                assert r["ok"], r

            # The child: in-process conductor on the wire, no source
            # fetcher — it can ONLY finish from surviving parents.
            child_host = Host(id="chaos-child", hostname="cc",
                              ip="127.0.0.1", port=8002, download_port=1)
            child_host.stats.network.idc = "idc-a"
            client = RemoteScheduler(server.url, timeout=3.0)
            storage = DaemonStorage(
                str(tmp_path / "childstore"), prefer_native=False
            )
            conductor = Conductor(
                child_host, storage, client,
                piece_fetcher=HTTPPieceFetcher(
                    client.resolve_host, timeout=3.0
                ),
                source_fetcher=None,
                max_piece_retries=8,
                piece_wait_timeout_s=20.0,
            )

            # Pace the child's fetches so the kill lands mid-download.
            scenario = ChaosScenario(faults=[
                FaultSpec(site="piece.fetch", kind="delay", every=1,
                          delay_s=0.15),
            ])
            result = {}

            def run_child():
                result["r"] = conductor.download(
                    url, piece_size=PIECE, content_length=len(blob)
                )

            with faultinject.installed(scenario.injector()):
                t = threading.Thread(target=run_child)
                t.start()
                # Mid-upload: the child has committed ≥1 piece and the
                # swarm is still serving it when parent 0 dies.
                wait_until(
                    lambda: storage.held_pieces(tid) >= 1,
                    timeout=60, desc="first piece committed",
                )
                daemons[0].sigkill()
                assert daemons[0].proc.returncode == -9
                t.join(timeout=120)
            assert not t.is_alive(), "child hung after parent kill"
            r = result["r"]
            assert r.ok, r
            assert not r.back_to_source  # finished from the swarm
            assert sha256_hex(storage.read_task_bytes(tid)) == sha256_hex(blob)
            # The dead parent was actually in play: failures were
            # reported and rescheduling happened around them.
            assert r.failed_pieces >= 1
        finally:
            for d in daemons:
                d.stop()
            server.stop()


# ---------------------------------------------------------------------------
# Drill 3b — piece data plane (PR 11): hedged straggler fetch + pooled
# connection eviction on parent death
# ---------------------------------------------------------------------------


class _PlaneOrigin:
    def content(self, url, number):
        seed = (hash(url) ^ number) & 0xFF
        return bytes((seed + i) % 251 for i in range(PIECE))

    def fetch(self, url, number, piece_size):
        return self.content(url, number)


class _PlaneNode:
    """In-process wire node for the data-plane drills: piece server +
    remote scheduler client + conductor (test_rpc.WireNode shape)."""

    def __init__(self, name, scheduler_url, tmp_path, origin=None, **conductor_kw):
        from dragonfly2_tpu.daemon import DaemonStorage, UploadManager
        from dragonfly2_tpu.daemon.conductor import Conductor
        from dragonfly2_tpu.rpc import HTTPPieceFetcher, RemoteScheduler
        from dragonfly2_tpu.rpc.piece_transport import PieceHTTPServer
        from dragonfly2_tpu.scheduler.resource import Host

        self.storage = DaemonStorage(str(tmp_path / name), prefer_native=False)
        self.upload = UploadManager(self.storage)
        self.server = PieceHTTPServer(self.upload)
        self.server.serve()
        self.host = Host(
            id=name, hostname=name, ip="127.0.0.1",
            download_port=self.server.port,
        )
        self.host.stats.network.idc = "idc-a"
        self.client = RemoteScheduler(scheduler_url)
        self.fetcher = HTTPPieceFetcher(self.client.resolve_host, timeout=5.0)
        self.conductor = Conductor(
            self.host, self.storage, self.client,
            piece_fetcher=self.fetcher, source_fetcher=origin,
            **conductor_kw,
        )

    def stop(self):
        self.server.stop()
        self.fetcher.close()


def _plane_swarm(tmp_path):
    from dragonfly2_tpu.records.storage import Storage
    from dragonfly2_tpu.rpc.scheduler_server import SchedulerHTTPServer
    from dragonfly2_tpu.scheduler import (
        Evaluator,
        NetworkTopology,
        Resource,
        SchedulerService,
        Scheduling,
        SchedulingConfig,
    )

    resource = Resource()
    service = SchedulerService(
        resource,
        Scheduling(Evaluator(), SchedulingConfig(retry_interval=0)),
        Storage(str(tmp_path / "records"), buffer_size=1),
        NetworkTopology(resource.host_manager),
    )
    server = SchedulerHTTPServer(service)
    server.serve()
    return server


class _CountingStore:
    """DaemonStorage wrapper counting write_piece calls per number — the
    exactly-one-commit-per-piece witness for the hedge drill."""

    def __init__(self, inner):
        self._inner = inner
        self.writes = {}
        self._mu = threading.Lock()

    def write_piece(self, task_id, number, data):
        with self._mu:
            self.writes[number] = self.writes.get(number, 0) + 1
        return self._inner.write_piece(task_id, number, data)

    def __getattr__(self, name):
        return getattr(self._inner, name)


class TestHedgedStragglerDrill:
    N_PIECES = 8

    def test_slow_parent_hedge_wins_exactly_one_commit(self, tmp_path):
        from dragonfly2_tpu.daemon.piece_pipeline import PIECE_HEDGE_TOTAL

        server = _plane_swarm(tmp_path)
        origin = _PlaneOrigin()
        url = "https://origin/hedge-blob"
        blob = b"".join(origin.content(url, n) for n in range(self.N_PIECES))
        parents = [
            _PlaneNode(f"hparent-{i}", server.url, tmp_path, origin)
            for i in range(2)
        ]
        child = _PlaneNode(
            "hchild", server.url, tmp_path, None,
            # Aggressive hedging so the drill derives its threshold from
            # the first couple of fetches: baseline ~ms, floor 0.15 s.
            hedge_min_samples=2, hedge_floor_s=0.15, hedge_multiplier=3.0,
            max_piece_retries=4,
        )
        try:
            for p in parents:
                r = p.conductor.download(
                    url, piece_size=PIECE, content_length=len(blob)
                )
                assert r.ok  # first seeds from origin, second via p2p
            counting = _CountingStore(child.storage)
            child.conductor.storage = counting
            fired0 = PIECE_HEDGE_TOTAL.value(outcome="fired")
            # ONE straggler: piece.fetch call #5 stalls 2 s — far past
            # the hedge threshold, far under the piece timeout.  The
            # hedge (a later piece.fetch index) races the other parent.
            scenario = ChaosScenario(faults=[
                FaultSpec(site="piece.fetch", kind="delay", at=(5,),
                          delay_s=2.0),
            ])
            with faultinject.installed(scenario.injector()):
                result = child.conductor.download(url, piece_size=PIECE)
            assert result.ok and not result.back_to_source, result
            # Zero digest failures: crc checked at every read, whole
            # content byte-identical to the origin.
            assert sha256_hex(
                child.storage.read_task_bytes(result.task_id)
            ) == sha256_hex(blob)
            # The hedge actually fired...
            assert PIECE_HEDGE_TOTAL.value(outcome="fired") > fired0
            # ...and NEVER double-committed: exactly one write per piece.
            assert counting.writes == {
                n: 1 for n in range(self.N_PIECES)
            }, counting.writes
        finally:
            child.stop()
            for p in parents:
                p.stop()
            server.stop()


class TestParentDeathPoolEvictionDrill:
    N_PIECES = 8

    def test_dead_parent_evicted_from_pool_and_rescheduled(self, tmp_path):
        server = _plane_swarm(tmp_path)
        origin = _PlaneOrigin()
        url = "https://origin/pool-evict-blob"
        blob = b"".join(origin.content(url, n) for n in range(self.N_PIECES))
        parents = [
            _PlaneNode(f"kparent-{i}", server.url, tmp_path, origin)
            for i in range(2)
        ]
        child = _PlaneNode(
            "kchild", server.url, tmp_path, None,
            hedge_enabled=False, max_piece_retries=8,
            piece_wait_timeout_s=20.0, piece_parallelism=2,
        )
        try:
            for p in parents:
                r = p.conductor.download(
                    url, piece_size=PIECE, content_length=len(blob)
                )
                assert r.ok
            # Every fetch asked of the victim is held until it is dead,
            # and the other parent serves meanwhile: pieces go to the two
            # holders in turn by number, so one of the child's first two
            # fetches is the victim's.  The kill lands with a piece
            # committed and at least one still wanted of the dead parent.
            # (Held at the fetcher and not at the piece.fetch site, which
            # cannot tell the parents apart: a served piece's hold would
            # be reported as the survivor's piece cost, and one 0.5 s
            # among 2 ms ones makes is_bad_node drop the only parent left.)
            victim = parents[0]
            parent_dead = threading.Event()
            fetch = child.fetcher.fetch

            def fetch_held_for_victim(host_id, *args, **kw):
                if host_id == victim.host.id:
                    assert parent_dead.wait(60), "parent never killed"
                return fetch(host_id, *args, **kw)

            child.fetcher.fetch = fetch_held_for_victim
            result = {}

            def run_child():
                result["r"] = child.conductor.download(url, piece_size=PIECE)

            t = threading.Thread(target=run_child, daemon=True)
            t.start()
            wait_until(
                lambda: child.storage.held_pieces(
                    child.conductor._task_id(url, None)
                ) >= 1,
                timeout=30, desc="first piece committed",
            )
            # Parent death: the listener closes AND its established
            # keep-alive sockets sever (a SIGKILLed process's RSTs —
            # stop() alone lets handler threads drain gracefully).
            victim.server.stop()
            for conn in list(
                child.fetcher.pool._idle.get(victim.host.id, [])
            ):
                conn.sock.close()
            parent_dead.set()
            t.join(timeout=60)
            assert not t.is_alive(), "child hung after parent kill"
            r = result["r"]
            assert r.ok and not r.back_to_source, r
            assert sha256_hex(
                child.storage.read_task_bytes(r.task_id)
            ) == sha256_hex(blob)
            # The reschedule path ran: failures were reported against the
            # dead parent and the pool holds NO connection to it.
            assert r.failed_pieces >= 1
            assert child.fetcher.pool.idle_count(victim.host.id) == 0
            # The surviving parent's connection(s) are still pooled.
            assert child.fetcher.pool.idle_count(parents[1].host.id) >= 1
        finally:
            child.stop()
            for p in parents:
                p.stop()
            server.stop()


# ---------------------------------------------------------------------------
# Drill 4 — trainer crash mid-online-ingest → orbax resume, exactly-once
# ---------------------------------------------------------------------------


class TestTrainerCrashDrill:
    TOTAL_DISPATCHES = 6
    CRASH_AT = 3

    def test_orbax_resume_no_duplicate_no_lost_records(self, tmp_path):
        import os
        import sys

        child = os.path.join(os.path.dirname(__file__), "_chaos_child.py")
        ckpt = str(tmp_path / "ckpt")

        # Phase 1: the trainer SIGKILLs ITSELF at dispatch index 3 (the
        # crash fault on the trainer.dispatch seam) — dispatches 0..2
        # trained and checkpointed, the stream position mid-flight.
        p1 = ChaosProcess(
            [child, "fresh", ckpt, str(self.TOTAL_DISPATCHES)],
            scenario=crash_at("trainer.dispatch", self.CRASH_AT),
            ready_prefixes=["chaos-child: ready"],
        ).start()
        p1.wait_ready(120)
        assert p1.wait_dead(300) == -9, p1.lines[-5:]
        assert os.path.isdir(os.path.join(ckpt, "online_graph"))

        # Phase 2: a fresh process resumes from the checkpoint and
        # finishes the stream, skipping exactly what was already trained.
        p2 = ChaosProcess(
            [child, "resume", ckpt, str(self.TOTAL_DISPATCHES)],
        ).start()
        assert p2.wait_dead(300) == 0, p2.lines[-8:]
        out = json.loads([l for l in p2.lines if l.startswith("{")][-1])
        resumed = [l for l in p2.lines if "resumed at dispatch" in l]
        assert resumed and resumed[0].endswith(str(self.CRASH_AT))

        # Exactly-once accounting: every record trained once, none lost.
        import _chaos_child as cc

        assert out["dispatch"] == self.TOTAL_DISPATCHES
        assert out["records_seen"] == self.TOTAL_DISPATCHES * cc.PER_DISPATCH

        # Byte-identity against an UNINTERRUPTED run of the same stream
        # (in-process — same platform config as the children).
        ref = cc.run("fresh", str(tmp_path / "ref_ckpt"), self.TOTAL_DISPATCHES)
        assert ref["records_seen"] == out["records_seen"]
        assert ref["state_hash"] == out["state_hash"]


# ---------------------------------------------------------------------------
# Drill 5 — scheduler SIGKILLed mid-announce → columnar rebuild, no torn rows
# ---------------------------------------------------------------------------


class TestColumnarRebuildDrill:
    """ISSUE 7: the columnar host store is the source of truth for host
    serving state, and it is IN-MEMORY — a scheduler killed mid-announce
    loses it.  The restart contract is rebuild-from-announces: a fresh
    process replaying the announce stream must end with zero torn slot
    rows (every bound row byte-matches a recompute off the column-backed
    accessors, write stamps agree with the hosts' mutation counters) and
    with columnar rule scores still bit-equal to the scalar oracle."""

    def test_kill_mid_announce_then_rebuild_has_no_torn_rows(self):
        import os

        child = os.path.join(os.path.dirname(__file__), "_columnar_child.py")

        # Phase 1: announce storm against the live columnar store; the
        # SIGKILL lands while announcer threads are mid-write.
        p1 = ChaosProcess(
            [child, "hammer"], ready_prefixes=["columnar-child: ready"],
        ).start()
        p1.wait_ready(120)
        time.sleep(0.5)  # the storm is genuinely mid-announce
        p1.sigkill()
        assert p1.wait_dead(60) == -9

        # Phase 2: the "restarted" scheduler — a fresh process — rebuilds
        # columnar state from the (deterministic) announce stream and
        # self-validates.
        p2 = ChaosProcess([child, "rebuild"]).start()
        assert p2.wait_dead(300) == 0, p2.lines[-8:]
        verdict = json.loads([l for l in p2.lines if l.startswith("{")][-1])
        assert verdict["torn"] == []
        assert verdict["rows_checked"] > 0
        assert verdict["row_mismatch"] == 0
        assert verdict["scores_bit_equal"] is True


# ---------------------------------------------------------------------------
# Satellites
# ---------------------------------------------------------------------------


class TestSchedulerBatcherFaultSeam:
    """ISSUE 3 satellite: a dropped/delayed coalesced scorer batch
    degrades to per-request scoring — announces never stall on the
    batcher (seam ``scheduler.eval.batch``, DF004 inventory)."""

    def _swarm(self):
        import numpy as np

        from dragonfly2_tpu.scheduler import (
            HostFeatureCache,
            MLEvaluator,
            ScorerBatcher,
        )
        from dragonfly2_tpu.sim.swarm import build_announce_swarm

        task, peers = build_announce_swarm(48, seed=11)

        class MLP:
            def __init__(self):
                rng = np.random.default_rng(0)
                self.w = rng.standard_normal((32, 1)).astype(np.float32)

            def score(self, features, **_buckets):
                return (np.asarray(features, np.float32) @ self.w)[..., 0]

        batcher = ScorerBatcher(linger_s=0.005)
        ml = MLEvaluator(
            MLP(), feature_cache=HostFeatureCache(max_hosts=256),
            batcher=batcher,
        )
        return task, peers, ml, batcher

    def _announce_storm(self, task, peers, ml, n_threads=8, per_thread=12):
        import numpy as np

        results, errs = [], []

        def worker(tid):
            rng = np.random.default_rng(tid)
            try:
                for _ in range(per_thread):
                    child_i = int(rng.integers(0, len(peers)))
                    cand = rng.choice(len(peers) - 1, size=9, replace=False)
                    cand = [c if c < child_i else c + 1 for c in cand]
                    ranked = ml.evaluate_parents(
                        [peers[c] for c in cand], peers[child_i],
                        task.total_piece_count,
                    )
                    results.append((child_i, tuple(cand),
                                    tuple(p.id for p in ranked)))
            except Exception as exc:  # noqa: BLE001
                errs.append(exc)

        threads = [
            threading.Thread(target=worker, args=(i,), daemon=True)
            for i in range(n_threads)
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return results, errs, time.monotonic() - t0

    def test_dropped_batch_degrades_to_per_request(self):
        task, peers, ml, batcher = self._swarm()
        scenario = ChaosScenario(faults=[
            FaultSpec(site="scheduler.eval.batch", kind="drop", every=2),
        ])
        with faultinject.installed(scenario.injector()) as inj:
            results, errs, _ = self._announce_storm(task, peers, ml)
        assert errs == []
        assert len(results) == 8 * 12          # every announce completed
        assert batcher.fallbacks >= 1          # the degrade path actually ran
        assert any(k[0] == "scheduler.eval.batch" for k in inj.history_keys())
        # Degraded (per-request) rankings are the SAME rankings the intact
        # coalesced path produces — the fault changes latency, not order.
        for child_i, cand, ranked in results:
            ref = ml._evaluate_parents_reference(
                [peers[c] for c in cand], peers[child_i],
                task.total_piece_count,
            )
            assert tuple(p.id for p in ref) == ranked

    def test_delayed_batch_does_not_stall_announces(self):
        task, peers, ml, batcher = self._swarm()
        scenario = ChaosScenario(faults=[
            FaultSpec(site="scheduler.eval.batch", kind="delay",
                      every=3, delay_s=0.05),
        ])
        with faultinject.installed(scenario.injector()):
            results, errs, wall = self._announce_storm(task, peers, ml)
        assert errs == []
        assert len(results) == 8 * 12
        # Delays pushed through the coalesced path, bounded, not a stall.
        assert wall < 30.0


class _FakeIdPTransport:
    """OAuth transport double: token endpoint + profile endpoint with
    scriptable outcomes."""

    def __init__(self):
        self.token_hits = 0
        self.profile_hits = 0
        self.token_delay_s = 0.0
        self.profile_error = None  # HTTP status to raise, or None
        self.rotate_to = None      # refresh_token rotation

    def __call__(self, req, timeout):
        url = req.full_url
        if "token" in url:
            self.token_hits += 1
            if self.token_delay_s:
                time.sleep(self.token_delay_s)
            body = {"access_token": "at-1"}
            if self.rotate_to:
                body["refresh_token"] = self.rotate_to
            return _Resp(body)
        self.profile_hits += 1
        if self.profile_error is not None:
            import io

            raise urllib.error.HTTPError(
                url, self.profile_error, "err", None, io.BytesIO(b"")
            )
        return _Resp({"email": "u@x", "login": "u"})


class _Resp:
    def __init__(self, body):
        self._body = json.dumps(body).encode()

    def read(self):
        return self._body

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _oauth(transport):
    from dragonfly2_tpu.manager.oauth import OAuthProvider, OAuthSignin
    from dragonfly2_tpu.manager.users import UserStore

    users = UserStore(db_path=None)
    oauth = OAuthSignin(users, transport=transport)
    oauth.register(OAuthProvider(
        name="prov", client_id="c", client_secret="s",
        auth_url="https://idp/auth", token_url="https://idp/token",
        profile_url="https://idp/profile",
    ))
    return oauth


class TestOAuthRefreshHardening:
    def test_handle_single_use_one_idp_redemption_under_race(self):
        tr = _FakeIdPTransport()
        tr.token_delay_s = 0.3
        oauth = _oauth(tr)
        rid = oauth._store_grant("prov", "uid-1", "rt-0")
        outcomes = []

        def go():
            try:
                outcomes.append(("ok", oauth.refresh(rid)[1]))
            except PermissionError as exc:
                outcomes.append(("denied", str(exc)))

        threads = [threading.Thread(target=go) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        # Exactly ONE redemption reached the IdP: a rotation-strict
        # provider sees one use of the refresh token, not token theft.
        assert tr.token_hits == 1
        assert sorted(o[0] for o in outcomes) == ["denied", "ok"]

    def test_profile_401_destroys_grant(self):
        tr = _FakeIdPTransport()
        tr.profile_error = 401
        oauth = _oauth(tr)
        rid = oauth._store_grant("prov", "uid-1", "rt-0")
        with pytest.raises(PermissionError):
            oauth.refresh(rid)
        assert rid not in oauth._grants  # destroyed → re-authenticate
        with pytest.raises(PermissionError):
            oauth.refresh(rid)  # unknown handle now

    def test_profile_5xx_is_transient_and_keeps_rotated_token(self):
        from dragonfly2_tpu.manager.oauth import OAuthUnavailable

        tr = _FakeIdPTransport()
        tr.profile_error = 503
        tr.rotate_to = "rt-1"
        oauth = _oauth(tr)
        rid = oauth._store_grant("prov", "uid-1", "rt-0")
        with pytest.raises(OAuthUnavailable):
            oauth.refresh(rid)
        # Grant survived AND carries the ROTATED token (rt-0 is dead at
        # the IdP after the redemption above).
        assert oauth._grants[rid][2] == "rt-1"
        # IdP recovers → the same handle refreshes fine.
        tr.profile_error = None
        user, new_rid = oauth.refresh(rid)
        assert user.name == "prov:u" and new_rid
        assert rid not in oauth._grants  # rotated handle

    def test_token_endpoint_outage_restores_grant(self):
        from dragonfly2_tpu.manager.oauth import OAuthUnavailable

        calls = []

        def down(req, timeout):
            calls.append(req.full_url)
            raise urllib.error.URLError("connection refused")

        oauth = _oauth(down)
        rid = oauth._store_grant("prov", "uid-1", "rt-0")
        with pytest.raises(OAuthUnavailable):
            oauth.refresh(rid)
        assert oauth._grants[rid][2] == "rt-0"  # intact, caller retries


class TestJobResultPersistence:
    def test_unserializable_result_persists_completion(self):
        from dragonfly2_tpu.jobs.queue import JobQueue, JobState
        from dragonfly2_tpu.manager.state import MemoryBackend

        backend = MemoryBackend()
        q = JobQueue(backend=backend)
        job = q.enqueue("preheat", {"urls": ["u"]}, queue_name="q-s")
        popped = q.poll("q-s", timeout=1.0)
        assert popped.id == job.id

        q.set_result(job.id, JobState.SUCCESS, result=object())  # not JSON

        # A restarted manager reloads the broker from the same backend:
        # the job is SUCCESS with result=None — NOT a STARTED row that
        # the stale-visibility requeue would guarantee-redeliver.
        q2 = JobQueue(backend=backend)
        reloaded = q2.jobs[job.id]
        assert reloaded.state is JobState.SUCCESS
        assert reloaded.result is None
        assert q2.poll("q-s", timeout=0.2, requeue_started_after_s=0.01) is None
