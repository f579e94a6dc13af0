"""Kill-the-manager-mid-preheat recovery drill (VERDICT r4 #5).

The manager concentrates durable state behind ONE backend
(manager/state.py): model registry rows, CRUD rows, the job broker, the
shared topology cache, users.  Reference: the manager spreads this over
MySQL/Postgres + Redis and survives restarts by construction
(manager/database/database.go:50-59).  This drill proves the embedded
backend gives the same story: a REAL manager process is SIGKILLed with
a preheat group in flight, restarted on the same state directory, and
every surface resumes —

- the preheat group survives and a late-attaching scheduler worker
  polls + completes it (jobs re-poll);
- pushed topology re-merges into replica pulls (topology re-merges);
- the cluster CA and its trust root are the SAME, so peer identities
  issued before the crash keep verifying and renewal retries succeed
  against the restarted manager (renewals retry);
- registry models and CRUD rows are intact.

DESIGN.md's failure-mode table cites this file in its "verified by"
column.
"""

import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from dragonfly2_tpu.sim.chaos import wait_until

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

try:
    import cryptography  # noqa: F401

    _HAS_CRYPTO = True
except ImportError:
    _HAS_CRYPTO = False


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _post(url: str, path: str, body: dict) -> dict:
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.loads(r.read() or b"{}")


def _get(url: str, path: str) -> dict:
    with urllib.request.urlopen(url + path, timeout=10) as r:
        return json.loads(r.read())


class _Manager:
    """A real cli.manager process on a FIXED port + state dir, so a
    restart is address-stable (clients retry the same endpoint).
    ``ha_yaml``/``extra_args`` configure the replication role (the
    leader+standby failover drill)."""

    def __init__(self, tmp: str, port: int, *, name: str = "manager",
                 ha_yaml: str = "", extra_args=()):
        self.tmp, self.port = tmp, port
        cfg_path = os.path.join(tmp, f"{name}.yaml")
        with open(cfg_path, "w") as f:
            f.write(
                f"server: {{host: 127.0.0.1, port: {port}, grpc_port: -1}}\n"
                f"registry: {{blob_dir: {tmp}/{name}}}\n"
                f"ca_dir: {tmp}/ca-{name}\n"
                "jobs_min_requeue_s: 0.01\n"
                + ha_yaml
            )
        self.cfg_path = cfg_path
        self.extra_args = list(extra_args)
        self.proc = None
        self.url = f"http://127.0.0.1:{port}"
        self.lines = []

    def start(self) -> None:
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "dragonfly2_tpu.cli.manager",
             "--config", self.cfg_path, *self.extra_args],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"},
        )
        ready = threading.Event()
        lines = self.lines = []

        def pump():
            for line in self.proc.stdout:
                lines.append(line)
                if line.startswith("manager: serving"):
                    ready.set()
            ready.set()  # EOF: the process is gone, stop waiting for it

        threading.Thread(target=pump, daemon=True).start()
        if not ready.wait(60) or self.proc.poll() is not None:
            raise AssertionError(
                f"manager never ready (exit {self.proc.poll()}): {lines[-10:]}"
            )

    def wait_role(self, role: str, *, applied_seq: int = 0) -> None:
        """Until ``replication:status`` reports ``role`` (and at least
        ``applied_seq`` applied)."""
        def reached() -> bool:
            status = _get(self.url, "/api/v1/replication:status")
            return status["role"] == role and status["applied_seq"] >= applied_seq

        wait_until(reached, timeout=60, interval=0.1, desc=f"{self.url} as {role}")

    def sigkill(self) -> None:
        self.proc.send_signal(signal.SIGKILL)
        self.proc.wait(timeout=30)

    def stop(self) -> None:
        if self.proc and self.proc.poll() is None:
            self.proc.terminate()
            self.proc.wait(timeout=30)


@pytest.mark.skipif(
    not _HAS_CRYPTO,
    reason="drill verifies CA trust-root survival; needs `cryptography`",
)
def test_kill_manager_mid_preheat_recovers(tmp_path):
    from dragonfly2_tpu.jobs.remote import RemoteJobClient, RemoteJobWorker
    from dragonfly2_tpu.security.ca import PeerIdentity

    mgr = _Manager(str(tmp_path), _free_port())
    mgr.start()
    try:
        client = RemoteJobClient(mgr.url)

        # --- stage the in-flight world ---------------------------------
        # 1. A preheat fanned to a scheduler queue whose worker has NOT
        #    attached yet — exactly the mid-preheat window.
        group = client.create_group(
            "preheat", {"urls": ["https://origin/blob"]}, ["q-sched-a"]
        )
        gid = group["group_id"]
        # 2. A scheduler's topology push (the shared probe graph).
        _post(mgr.url, "/api/v1/topology", {
            "scheduler_id": "sched-a",
            "edges": [{"src": "h1", "dst": "h2", "average_rtt_ns": 12345}],
        })
        # 3. A registered model (the registry surface).
        import base64

        _post(mgr.url, "/api/v1/models", {
            "name": "parent-bandwidth-mlp", "type": "mlp",
            "scheduler_id": "sched-a",
            "artifact_b64": base64.b64encode(b"npzbytes").decode(),
        })
        # 4. A CRUD row (cluster config override).
        _post(mgr.url, "/api/v1/clusters", {
            "id": "c1", "name": "c1",
            "scheduler_cluster_config": {"candidate_parent_limit": 7},
        })
        # 5. A peer identity issued by the cluster CA.
        ident = PeerIdentity.request_from_manager(
            mgr.url, common_name="daemon-a"
        )
        ca_pem_before = ident.ca_pem

        # --- the crash --------------------------------------------------
        mgr.sigkill()
        with pytest.raises(urllib.error.URLError):
            _get(mgr.url, "/api/v1/jobs")  # provably down

        # --- restart on the same state dir ------------------------------
        mgr.start()

        # Jobs re-poll: the group survived, and the late-attaching worker
        # completes it now.
        st = client.group_state(gid)
        assert st["state"] == "PENDING", st
        worker = RemoteJobWorker(mgr.url, "q-sched-a", poll_timeout_s=0.5)
        done = {}
        worker.register(
            "preheat", lambda args: done.setdefault("urls", args["urls"])
        )
        assert worker.poll_once() is True
        assert done["urls"] == ["https://origin/blob"]
        assert client.group_state(gid)["state"] == "SUCCESS"

        # Topology re-merges: a replica's pull still sees sched-a's edge.
        edges = _get(mgr.url, "/api/v1/topology?exclude=sched-b")["edges"]
        assert any(
            e["src"] == "h1" and e["average_rtt_ns"] == 12345 for e in edges
        ), edges

        # Registry + CRUD intact.
        models = _get(mgr.url, "/api/v1/models")
        assert any(m["name"] == "parent-bandwidth-mlp" for m in models), models
        cluster_cfg = _get(mgr.url, "/api/v1/clusters/c1:config")
        assert cluster_cfg["scheduler_cluster_config"] == {
            "candidate_parent_limit": 7
        }

        # Renewals retry: the SAME trust root signs after restart, so the
        # pre-crash identity still verifies and a renewal succeeds.
        renewed = PeerIdentity.request_from_manager(
            mgr.url, common_name="daemon-a"
        )
        assert renewed.ca_pem == ca_pem_before
    finally:
        mgr.stop()


def test_started_job_redelivers_after_restart(tmp_path):
    """The at-least-once contract across a crash: a job a worker POPPED
    (STARTED) before the manager died re-delivers after restart through
    the stale-visibility requeue — the worker's completion was lost with
    the broker, so the job must run again, not vanish."""
    from dragonfly2_tpu.jobs.remote import RemoteJobClient, RemoteJobWorker

    mgr = _Manager(str(tmp_path), _free_port())
    mgr.start()
    try:
        client = RemoteJobClient(mgr.url)
        group = client.create_group("preheat", {"urls": ["u"]}, ["q-s"])
        gid = group["group_id"]
        # Pop WITHOUT reporting: the broker marks it STARTED durably.
        job = _post(mgr.url, "/api/v1/jobs:poll", {"queue": "q-s",
                                                   "timeout_s": 2})
        assert job["id"]
        mgr.sigkill()
        mgr.start()
        st = client.group_state(gid)
        assert st["jobs"][0]["state"] == "STARTED"  # reloaded as popped
        # A fresh poll inside the visibility window yields nothing...
        worker = RemoteJobWorker(mgr.url, "q-s", poll_timeout_s=0.3)
        worker.register("preheat", lambda args: "done")
        assert worker.poll_once() is False
        # ...and the broker's stale-started requeue re-delivers it once
        # the window passes (shrunk via the poll parameter).
        job2 = _post(mgr.url, "/api/v1/jobs:poll", {
            "queue": "q-s", "timeout_s": 2, "requeue_started_after_s": 0.01,
        })
        assert job2["id"] == job["id"]
    finally:
        mgr.stop()


def test_leader_sigkill_with_standby_fails_over_zero_pinning(tmp_path):
    """The Manager-HA acceptance drill (ISSUE 9 / DESIGN.md §20): the
    leader is SIGKILLed mid-preheat with a hot standby attached and is
    NEVER restarted —

    - the standby promotes itself on lease expiry (term 2);
    - the in-flight preheat completes through the promoted follower
      (job rows replicated, worker polls the endpoint pair);
    - the dynconfig payload and the model registry (row + digest-checked
      artifact) keep serving through the standby;
    - the ModelSubscriber's poll NEVER engages the PR-4 pin-to-last-
      ACTIVE degraded mode (``pinned`` stays False throughout).
    """
    import numpy as np

    from dragonfly2_tpu.jobs.remote import RemoteJobClient, RemoteJobWorker
    from dragonfly2_tpu.records.features import DOWNLOAD_FEATURE_DIM
    from dragonfly2_tpu.rpc.registry_client import RemoteRegistry
    from dragonfly2_tpu.scheduler import MLEvaluator, ModelSubscriber
    from dragonfly2_tpu.trainer.export import MLPScorer, scorer_to_bytes

    ha_yaml = (
        "ha: {enable: true, lease_ttl_s: 2.0, poll_interval_s: 0.25, "
        "lease_secret: drill-secret-0123456789abcdef}\n"
    )
    leader = _Manager(str(tmp_path), _free_port(), name="leader",
                      ha_yaml=ha_yaml)
    leader.start()
    standby = _Manager(
        str(tmp_path), _free_port(), name="standby", ha_yaml=ha_yaml,
        extra_args=["--replicate-from", leader.url],
    )
    standby.start()
    pair = f"{leader.url},{standby.url}"
    try:
        leader.wait_role("leader")
        standby.wait_role("standby")
        client = RemoteJobClient(pair)

        # --- stage the in-flight world on the LEADER --------------------
        group = client.create_group(
            "preheat", {"urls": ["https://origin/blob"]}, ["q-sched-a"]
        )
        gid = group["group_id"]
        rng = np.random.default_rng(0)
        weights = [(
            rng.standard_normal((DOWNLOAD_FEATURE_DIM, 1)).astype(np.float32),
            np.zeros(1, dtype=np.float32),
        )]
        artifact = scorer_to_bytes(MLPScorer(weights=weights))
        import base64

        created = _post(leader.url, "/api/v1/models", {
            "name": "parent-bandwidth-mlp", "type": "mlp",
            "scheduler_id": "sched-a",
            "artifact_b64": base64.b64encode(artifact).decode(),
        })
        _post(leader.url, f"/api/v1/models/{created['id']}:activate", {})

        # A subscriber polling through the endpoint pair, synced once
        # while the leader is alive.
        remote = RemoteRegistry(pair, timeout=5.0)
        subscriber = ModelSubscriber(
            remote, MLEvaluator(), scheduler_id="sched-a",
        )
        assert subscriber.refresh() is True
        assert subscriber.pinned is False

        # The follower has tailed every staged row before the crash.
        staged = _get(leader.url, "/api/v1/replication:status")["seq"]
        assert staged >= 1
        standby.wait_role("standby", applied_seq=staged)

        # --- the crash: SIGKILL the leader, never restart it ------------
        leader.sigkill()

        # Reads fail over immediately (standby answers them pre-
        # promotion); the poll must NOT pin.
        assert subscriber.refresh() is False  # unchanged version
        assert subscriber.pinned is False, (
            "subscriber pinned with a live standby attached"
        )

        # The standby promotes on lease expiry and the in-flight preheat
        # completes THROUGH it.
        worker = RemoteJobWorker(pair, "q-sched-a", poll_timeout_s=0.5)
        done = {}
        worker.register(
            "preheat", lambda args: done.setdefault("urls", args["urls"])
        )
        deadline = time.time() + 30
        completed = False
        while time.time() < deadline and not completed:
            try:
                completed = worker.poll_once()
            except ConnectionError:
                time.sleep(0.3)
        assert completed, (
            "preheat never drained through the promoted follower",
            standby.lines[-10:],
        )
        assert done["urls"] == ["https://origin/blob"]
        assert client.group_state(gid)["state"] == "SUCCESS"

        # Promotion is observable: role leader, term advanced.
        status = _get(standby.url, "/api/v1/replication:status")
        assert status["role"] == "leader" and status["term"] >= 2

        # Registry row + digest-verified artifact through the survivor.
        model = remote.active_model("sched-a", "parent-bandwidth-mlp")
        assert model is not None
        assert remote.load_artifact(model) == artifact

        # Dynconfig payload (cluster config) still serving.
        cfg = _get(standby.url, "/api/v1/clusters/default:config")
        assert "scheduler_cluster_config" in cfg

        # And the subscriber STILL never pinned.
        subscriber.refresh()
        assert subscriber.pinned is False
    finally:
        leader.stop()
        standby.stop()


def test_legacy_sqlite_layouts_migrate_once(tmp_path):
    """Pre-seam deployments kept per-store files with typed tables; an
    upgraded manager imports them into the kv backend instead of
    silently booting empty — and never re-imports over newer rows."""
    import sqlite3

    from dragonfly2_tpu.manager.crud import CrudStore
    from dragonfly2_tpu.manager.registry import ModelRegistry
    from dragonfly2_tpu.manager.state import SQLiteBackend, migrate_legacy_sqlite
    from dragonfly2_tpu.manager.users import UserStore

    models_db = str(tmp_path / "manager.db")
    conn = sqlite3.connect(models_db)
    conn.execute(
        "CREATE TABLE models (id TEXT PRIMARY KEY, name TEXT, type TEXT, "
        "version INTEGER, scheduler_id TEXT, state TEXT, evaluation TEXT, "
        "blob_key TEXT, created_at REAL, updated_at REAL)"
    )
    conn.execute(
        "INSERT INTO models VALUES ('m1-v1','ranker','gnn',1,'s1',"
        "'active','{\"mae\": 0.5}','b1',1.0,2.0)"
    )
    conn.commit(); conn.close()

    crud_db = str(tmp_path / "crud.db")
    conn = sqlite3.connect(crud_db)
    conn.execute(
        "CREATE TABLE crud_rows (kind TEXT, id TEXT, value TEXT, "
        "PRIMARY KEY (kind, id))"
    )
    conn.execute(
        "INSERT INTO crud_rows VALUES ('application','a1',"
        "'{\"id\": \"a1\", \"name\": \"app\", \"url\": \"\", "
        "\"bio\": \"\", \"priority\": 1}')"
    )
    conn.commit(); conn.close()

    users_db = str(tmp_path / "users.db")
    legacy_users = UserStore(db_path=None)  # build hashes via the real path
    conn = sqlite3.connect(users_db)
    conn.execute(
        "CREATE TABLE users (id TEXT PRIMARY KEY, name TEXT, email TEXT, "
        "role INTEGER, state TEXT, password_hash BLOB, salt BLOB, "
        "created_at REAL)"
    )
    conn.execute(
        "INSERT INTO users VALUES ('user-1','root','', 2,'enabled',?,?,1.0)",
        (b"\x01\x02", b"\x03\x04"),
    )
    conn.execute(
        "CREATE TABLE pats (id TEXT PRIMARY KEY, user_id TEXT, name TEXT, "
        "role INTEGER, token_hash TEXT, expires_at REAL, revoked INTEGER, "
        "created_at REAL)"
    )
    conn.commit(); conn.close()

    backend = SQLiteBackend(str(tmp_path / "manager-state.db"))
    counts = migrate_legacy_sqlite(
        backend, models_db=models_db, crud_db=crud_db, users_db=users_db
    )
    assert counts == {"models": 1, "crud": 1, "users": 1}

    reg = ModelRegistry(backend=backend)
    m = reg.get("m1-v1")
    assert m and m.name == "ranker" and m.evaluation == {"mae": 0.5}
    crud = CrudStore(backend=backend)
    assert crud.get("application", "a1").priority == 1
    users = UserStore(backend=backend)
    assert users.by_name("root") is not None
    assert users._creds["user-1"] == (b"\x01\x02", b"\x03\x04")

    # Idempotent: a second boot (rows now present) imports nothing.
    assert migrate_legacy_sqlite(
        backend, models_db=models_db, crud_db=crud_db, users_db=users_db
    ) == {}


def test_crash_between_registry_flip_and_rollout_row(tmp_path):
    """DF014 crash-between-rows drill for the ``rollouts`` table: the
    registry flip (models table, transactional) and the rollout row
    (rollouts table) cannot share a transaction, so ``begin`` can crash
    AFTER the candidate went SHADOW but BEFORE its rollout row
    committed.  Without repair, every evaluation report would KeyError
    forever against a candidate the scheduler can see.  The reloaded
    controller must reconcile: adopt the orphan candidate so the
    rollout is judgeable again (declared invariant
    'no_dangling_rollout')."""
    from dragonfly2_tpu.manager.registry import ModelRegistry, ModelState
    from dragonfly2_tpu.manager.state import SQLiteBackend
    from dragonfly2_tpu.rollout.controller import RolloutController
    from dragonfly2_tpu.utils import faultinject

    db = str(tmp_path / "state.db")
    backend = SQLiteBackend(db)
    registry = ModelRegistry(backend=backend)
    active = registry.create_model(
        name="ranker", type="mlp", scheduler_id="s1", artifact=b"\x01" * 4,
    )
    registry.activate(active.id)
    candidate = registry.create_model(
        name="ranker", type="mlp", scheduler_id="s1", artifact=b"\x02" * 4,
    )
    controller = RolloutController(registry, backend=backend)
    inj = faultinject.FaultInjector([
        faultinject.FaultSpec(site="state.put.rollouts", kind="drop", at=(0,)),
    ])
    with faultinject.installed(inj):
        with pytest.raises(ConnectionError):
            controller.begin(candidate.id)
    # The tear is real: the registry committed the SHADOW flip, the
    # rollouts table has no row.
    assert registry.get(candidate.id).state is ModelState.SHADOW
    assert backend.table("rollouts").load_all() == {}
    backend.close()

    # Restart: reload BOTH consumers from the same file.
    backend = SQLiteBackend(db)
    registry2 = ModelRegistry(backend=backend)
    controller2 = RolloutController(registry2, backend=backend)
    rollout = controller2.get("s1", "ranker")
    assert rollout is not None, "orphan SHADOW candidate was not adopted"
    assert rollout.model_id == candidate.id
    assert rollout.phase == "shadow"
    assert rollout.previous_active_id == active.id
    # The adopted row is durable AND judgeable: a report flows.
    decision = controller2.report("s1", "ranker", {"joined_edges": 1})
    assert decision["decision"] == "hold"
    backend.close()

    # And the row survives the NEXT restart as a plain reload (no
    # re-adoption path needed).
    backend = SQLiteBackend(db)
    registry3 = ModelRegistry(backend=backend)
    controller3 = RolloutController(registry3, backend=backend)
    r3 = controller3.get("s1", "ranker")
    assert r3 is not None and r3.model_id == candidate.id
    assert r3.reason == "adopted during crash recovery"
    backend.close()


def test_crash_between_promote_and_rollout_row(tmp_path):
    """The other tear direction: ``_advance`` to ACTIVE commits the
    registry's single-active flip, then crashes before the rollout row
    records the phase.  On reload the row must follow the registry
    (phase 'active'), not replay the canary judgement."""
    from dragonfly2_tpu.manager.registry import ModelRegistry, ModelState
    from dragonfly2_tpu.manager.state import SQLiteBackend
    from dragonfly2_tpu.rollout.controller import (
        RolloutController, RolloutGuardrails,
    )
    from dragonfly2_tpu.utils import faultinject

    db = str(tmp_path / "state.db")
    backend = SQLiteBackend(db)
    registry = ModelRegistry(backend=backend)
    candidate = registry.create_model(
        name="ranker", type="mlp", scheduler_id="s1", artifact=b"\x02" * 4,
    )
    rails = RolloutGuardrails(min_shadow_samples=1, min_canary_samples=1)
    controller = RolloutController(registry, guardrails=rails, backend=backend)
    controller.begin(candidate.id)
    clean = {
        "joined_edges": 10,
        "regret_at_k": {"candidate": 0.0, "active": 0.0, "k": 3},
        "inversion_rate": {"candidate": 0.0, "active": 0.0},
        "psi_max": 0.0,
    }
    assert controller.report("s1", "ranker", clean)["decision"] == "advance"
    # Promote: the registry flip (put_many on models) commits, the
    # rollout-row put is dropped.
    clean2 = dict(clean, joined_edges=20)
    inj = faultinject.FaultInjector([
        faultinject.FaultSpec(site="state.put.rollouts", kind="drop", at=(0,)),
    ])
    with faultinject.installed(inj):
        with pytest.raises(ConnectionError):
            controller.report("s1", "ranker", clean2)
    assert registry.get(candidate.id).state is ModelState.ACTIVE
    backend.close()

    backend = SQLiteBackend(db)
    registry2 = ModelRegistry(backend=backend)
    controller2 = RolloutController(registry2, backend=backend)
    rollout = controller2.get("s1", "ranker")
    assert rollout is not None
    assert rollout.phase == "active", (
        "rollout row must follow the committed registry promote",
        rollout.phase,
    )
    assert "reconciled" in rollout.reason
    backend.close()
