"""manager service binary (reference: cmd/manager + manager/manager.go).

Boots the control-plane composition: model registry (versioned blobs),
cluster manager with keepalive TTLs, searcher, dynconfig server, job
broker.  ``--list-models DIR`` prints the registry persisted under DIR
(the ops inspection path the reference serves via console/REST).
"""

from __future__ import annotations

import sys
import time

from ..config import ManagerConfig, load_config
from ..jobs import JobQueue
from ..manager import ClusterManager, ModelRegistry, Searcher
from ..manager.registry import BlobStore
from .common import (
    base_parser,
    init_debug,
    init_flight_recorder,
    init_telemetry,
    init_logging,
    init_tracing,
)


def _build_consumers(cfg: ManagerConfig, backend, blob_store):
    """The backend-fed composition pieces: rebuilt wholesale by the
    standby every time the replication follower applies a batch (their
    in-memory caches must track the replicated rows)."""
    from ..manager.crud import CrudStore
    from ..rollout import RolloutController, RolloutGuardrails

    registry = ModelRegistry(blob_store, backend=backend)
    crud = CrudStore(backend=backend)
    rollout = RolloutController(
        registry,
        guardrails=RolloutGuardrails(
            min_shadow_samples=cfg.rollout.min_shadow_samples,
            min_canary_samples=cfg.rollout.min_canary_samples,
            max_regret_ratio=cfg.rollout.max_regret_ratio,
            regret_slack=cfg.rollout.regret_slack,
            max_inversion_ratio=cfg.rollout.max_inversion_ratio,
            max_psi=cfg.rollout.max_psi,
            canary_percent=cfg.rollout.canary_percent,
        ),
        backend=backend,
    )
    return {
        "registry": registry,
        "crud": crud,
        "rollout": rollout,
        "jobs": JobQueue(backend=backend),
    }


def build(cfg: ManagerConfig, *, replicate_from: str = ""):
    import os
    import socket as _socket

    # ONE durable state backend for every manager surface (manager/
    # state.py seam): registry rows, CRUD rows, the job broker, the
    # shared topology cache, users — a restart reloads all of it from
    # one place, and the HA story swaps one backend, not five files.
    from ..manager.state import make_state_backend, migrate_legacy_sqlite

    replicate_from = replicate_from or cfg.ha.replicate_from
    ha_enabled = bool(cfg.ha.enable or replicate_from)
    backend = make_state_backend(
        os.path.join(cfg.registry.blob_dir, "manager-state.db")
    )
    ha = lease_keeper = None
    if ha_enabled:
        from ..manager.replication import LeaseKeeper, ReplicatedStateBackend

        role = "standby" if replicate_from else "leader"
        node_id = cfg.ha.node_id or (
            f"mgr-{_socket.gethostname()}-{cfg.server.port}"
        )
        ha = backend = ReplicatedStateBackend(
            backend,
            node_id=node_id,
            role=role,
            lease_ttl_s=cfg.ha.lease_ttl_s,
            lease_secret=cfg.ha.lease_secret,
        )
        if role == "leader":
            # The lease runs from the backend's construction, and the
            # rest of the boot writes under it (default cluster, root
            # user): renew from here, not from when REST is up, or a
            # boot slower than the TTL refuses its own first write.
            lease_keeper = LeaseKeeper(ha)
            lease_keeper.serve()
    if not replicate_from:
        # Pre-seam deployments kept per-store files; import them once so
        # an upgrade never silently drops models/CRUD rows.  A standby
        # never migrates — its state comes from the leader's snapshot.
        migrated = migrate_legacy_sqlite(
            backend,
            models_db=os.path.join(cfg.registry.blob_dir, "manager.db"),
            crud_db=os.path.join(cfg.registry.blob_dir, "crud.db"),
        )
        if migrated:
            print(f"manager: migrated legacy state {migrated}", flush=True)
    # HA replicates artifacts WITH their registry rows (KVBlobStore rides
    # the same log); the single-node form keeps the blob directory.
    if ha_enabled:
        from ..manager.registry import KVBlobStore

        blob_store = KVBlobStore(backend)
    else:
        blob_store = BlobStore(cfg.registry.blob_dir)
    clusters = ClusterManager(keepalive_ttl=cfg.keepalive_ttl_s)
    objectstorage = None
    if cfg.objectstorage:
        from ..objectstorage import make_backend

        kwargs = dict(cfg.objectstorage)
        objectstorage = make_backend(kwargs.pop("kind", "fs"), **kwargs)
    # Rollout controller (rollout/controller.py): evidence-gated
    # SHADOW→CANARY→ACTIVE promotion with auto-rollback; its rows ride
    # the same state backend, so in-flight rollouts survive a bounce.
    # On a standby the boot-time reconciliation runs under applying()
    # (derived state, not new client mutations).
    if ha is not None and ha.role == "standby":
        with ha.applying():
            consumers = _build_consumers(cfg, backend, blob_store)
    else:
        consumers = _build_consumers(cfg, backend, blob_store)
        consumers["crud"].ensure_default_cluster()
    # NOTE: no DynconfigServer here — the dynconfig payload schedulers
    # poll is served straight from the CrudStore's cluster rows
    # (/api/v1/clusters/<id>:config), one source of truth.
    return {
        "registry": consumers["registry"],
        "clusters": clusters,
        "searcher": Searcher(),
        "jobs": consumers["jobs"],
        "crud": consumers["crud"],
        "objectstorage": objectstorage,
        "state_backend": backend,
        "rollout": consumers["rollout"],
        "ha": ha,
        "lease_keeper": lease_keeper,
        "blob_store": blob_store,
    }


def run(argv=None) -> int:
    p = base_parser("manager", "Control-plane manager service")
    p.add_argument("--list-models", action="store_true")
    p.add_argument(
        "--replicate-from", default="", metavar="URL",
        help="boot as a hot standby tailing this leader's replication "
             "log; promotes itself when the leader's lease expires",
    )
    args = p.parse_args(argv)
    init_logging(args, "manager")
    init_debug(args)
    init_tracing(args)

    cfg = load_config(ManagerConfig, args.config)
    init_flight_recorder(args, cfg.tracing, "manager")
    init_telemetry(args, cfg.telemetry, "manager")
    parts = build(cfg, replicate_from=args.replicate_from)

    if args.list_models:
        models = parts["registry"].list()
        if not models:
            print("manager: registry empty")
        for m in models:
            print(
                f"manager: {m.name} v{m.version} type={m.type} state={m.state.value} "
                f"scheduler={m.scheduler_id} eval={m.evaluation}"
            )
        return 0

    from ..manager.rest import ManagerRESTServer

    # A node configured as leader first asks its peers (if any) whether
    # a higher term already exists: followers PULL, so nothing would
    # otherwise deliver a successor's term to a restarted fenced leader
    # — it would boot at its stale term and accept writes again.  With a
    # higher term observed it demotes itself and tails that peer.
    replicate_from = args.replicate_from or cfg.ha.replicate_from
    ha = parts["ha"]
    if ha is not None and ha.role == "leader" and cfg.ha.peers:
        from ..manager.replication import probe_peer_term

        peer_term, peer_url = probe_peer_term(cfg.ha.peers)
        if peer_term > ha.term:
            ha.observe_term(peer_term)
            replicate_from = peer_url
            print(
                f"manager: peer {peer_url} holds term {peer_term}; "
                "joining as standby", flush=True,
            )

    auth = {}
    if cfg.token_secret:
        from ..manager.users import UserStore
        from ..security.tokens import TokenIssuer, TokenVerifier

        secret = cfg.token_secret.encode()
        # users_db (if set) keeps its own file for operators who isolate
        # credentials; default shares the one state backend.  Legacy
        # users/pats tables in that file import once.
        if cfg.users_db:
            from ..manager.state import SQLiteBackend, migrate_legacy_sqlite

            user_backend = SQLiteBackend(cfg.users_db)
            migrate_legacy_sqlite(user_backend, users_db=cfg.users_db)
            users = UserStore(backend=user_backend)
        else:
            users = UserStore(backend=parts["state_backend"])
        if cfg.root_password and not (
            parts["ha"] is not None and parts["ha"].role == "standby"
        ):
            # A standby never seeds accounts — the root user replicates
            # from the leader like every other row.
            users.ensure_root(cfg.root_password)
        auth = {
            "token_verifier": TokenVerifier(secret),
            "token_issuer": TokenIssuer(secret),
            "users": users,
        }
        if cfg.oauth_providers:
            from ..manager.oauth import OAuthProvider, OAuthSignin

            oauth = OAuthSignin(users)
            for p in cfg.oauth_providers:
                oauth.register(OAuthProvider(**p))
            auth["oauth"] = oauth
    from ..rpc.ratelimit import maybe_bucket

    bucket = maybe_bucket(cfg.server.rate_limit_qps, cfg.server.rate_limit_burst)
    ca = None
    if cfg.ca_dir:
        try:
            from ..security.ca import CertificateAuthority
        except ImportError:
            # `cryptography` absent: serve without the CA surface rather
            # than dying at boot — identity issuance degrades to 404,
            # everything else (registry, jobs, topology) keeps working.
            print("manager: ca_dir set but `cryptography` unavailable; "
                  "serving without CA", flush=True)
        else:
            # Persistent: restarts keep the cluster trust root, so issued
            # peer identities stay valid across a manager bounce.
            ca = CertificateAuthority.persistent(cfg.ca_dir)
    rest = ManagerRESTServer(
        parts["registry"], parts["clusters"], parts["searcher"],
        host=cfg.server.host, port=cfg.server.port,
        jobqueue=parts["jobs"], crud=parts["crud"],
        objectstorage=parts["objectstorage"],
        rate_limit=bucket,
        ca=ca,
        state_backend=parts["state_backend"],
        jobs_min_requeue_s=cfg.jobs_min_requeue_s,
        rollout=parts["rollout"],
        ha=parts["ha"],
        **auth,
    )
    rest.serve()
    # -- replication role (manager/replication.py, DESIGN.md §20) -------
    lease_keeper = parts["lease_keeper"]
    follower = None
    if ha is not None and ha.role != "leader" and replicate_from:
        from ..manager.replication import LeaseKeeper, LogFollower

        def _rebuild(_touched) -> None:
            # Replicated rows changed: swap the REST surface onto fresh
            # consumers (their in-memory caches reload from the backend).
            with ha.applying():
                fresh = _build_consumers(
                    cfg, parts["state_backend"], parts["blob_store"]
                )
            rest.registry = fresh["registry"]
            rest.rollout = fresh["rollout"]
            rest.crud = fresh["crud"]
            rest.jobqueue = fresh["jobs"]
            if rest._topology_table is not None:
                with rest._topology_mu:
                    rest.topology_shared = rest._topology_table.load_all()

        def _on_promote() -> None:
            # Now the leader: reconcile as a leader would at boot, start
            # renewing the lease, and let the standing 503 gate fall
            # away (the REST handler reads ha.role per request).
            fresh = _build_consumers(
                cfg, parts["state_backend"], parts["blob_store"]
            )
            fresh["crud"].ensure_default_cluster()
            rest.registry = fresh["registry"]
            rest.rollout = fresh["rollout"]
            rest.crud = fresh["crud"]
            rest.jobqueue = fresh["jobs"]
            keeper = LeaseKeeper(ha)
            keeper.serve()
            print(
                f"manager: promoted to leader (term {ha.term})", flush=True
            )

        follower = LogFollower(
            ha, replicate_from,
            poll_interval_s=cfg.ha.poll_interval_s,
            on_apply=_rebuild,
            on_promote=_on_promote,
        )
        follower.serve()
    grpc_server = None
    if cfg.server.grpc_port >= 0:
        from ..rpc.grpc_transport import ManagerGRPCServer

        grpc_server = ManagerGRPCServer(
            parts["registry"], parts["clusters"], parts["searcher"],
            host=cfg.server.host, port=cfg.server.grpc_port,
            # Same RBAC as REST, same credentials: session tokens AND PATs;
            # same SHARED rate-limit bucket (qps bounds the service).
            token_verifier=auth.get("token_verifier"),
            users=auth.get("users"),
            rate_limit=bucket,
            ca=ca,
        )
        grpc_server.serve()
    # flush: under a pipe (supervisors, e2e harnesses) the ready line must
    # be visible immediately, not at buffer-fill.
    print(
        f"manager: serving REST on {rest.url}"
        + (f" and grpc on {grpc_server.target}" if grpc_server else "")
        + (
            f" as {parts['ha'].role} (term {parts['ha'].term})"
            if parts["ha"] is not None else ""
        )
        + " (ctrl-c to stop)",
        flush=True,
    )
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        rest.stop()
        if grpc_server is not None:
            grpc_server.stop()
        if lease_keeper is not None:
            lease_keeper.stop()
        if follower is not None:
            follower.stop()
        return 0


if __name__ == "__main__":
    sys.exit(run())
