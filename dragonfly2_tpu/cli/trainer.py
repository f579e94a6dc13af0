"""trainer service binary (reference: cmd/trainer + trainer/trainer.go).

Boots the trainer composition (registry client, ingest service, training)
on a TPU-VM.  ``--train-once DIR`` ingests columnar shards from DIR and
runs one training round synchronously (the smoke/e2e mode); without it the
process serves and waits for announcer uploads.
"""

from __future__ import annotations

import glob
import os
import sys
import time

from ..config import TrainerConfigFile, load_config
from ..manager.registry import ModelRegistry
from ..trainer.service import TrainerService
from ..trainer.train import TrainConfig
from .common import (
    base_parser,
    init_debug,
    init_flight_recorder,
    init_telemetry,
    init_logging,
    init_tracing,
)


def run(argv=None) -> int:
    p = base_parser("trainer", "Model training service")
    p.add_argument("--train-once", default=None, metavar="DIR",
                   help="ingest DIR's columnar shards, train one round, exit")
    p.add_argument("--scheduler-id", default="scheduler-local")
    p.add_argument("--manager", default=None, metavar="URL",
                   help="remote manager REST URL (models publish there)")
    p.add_argument("--manager-token", default=None, help="bearer token for the manager")
    args = p.parse_args(argv)
    init_logging(args, "trainer")
    init_debug(args)
    init_tracing(args)

    cfg = load_config(TrainerConfigFile, args.config)
    init_flight_recorder(args, cfg.tracing, "trainer")
    init_telemetry(args, cfg.telemetry, "trainer")
    # This is the process that owns the accelerator: say which device it
    # got, so a trainer that came up on the CPU cannot pass for one on
    # the chip, and where its compiled programs persist.
    import jax

    from ..utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    print(
        f"trainer: device {dev.platform} ({dev.device_kind}) "
        f"x{len(jax.devices())}, compile cache {cache_dir}",
        flush=True,
    )
    manager_addr = args.manager or cfg.manager_addr
    if manager_addr and manager_addr.startswith("grpc://"):
        from ..rpc.grpc_transport import GRPCRemoteRegistry

        registry = GRPCRemoteRegistry(
            manager_addr[len("grpc://"):], token=args.manager_token or ""
        )
    elif manager_addr:
        from ..rpc import RemoteRegistry

        registry = RemoteRegistry(manager_addr, token=args.manager_token)
    else:
        registry = ModelRegistry()
    service = TrainerService(
        registry,
        # --train-once reads local shards (no staging); serve mode ingests
        # remote uploads into data_dir.
        data_dir=None if args.train_once else cfg.data_dir,
        train_config=TrainConfig(
            epochs=cfg.training.epochs,
            learning_rate=cfg.training.learning_rate,
            warmup_steps=cfg.training.warmup_steps,
        ),
    )

    if args.train_once:
        session = service.open_train_stream(
            ip="127.0.0.1", hostname=os.uname().nodename, scheduler_id=args.scheduler_id
        )
        dl = sorted(glob.glob(os.path.join(args.train_once, "download*.dfc")))
        topo = sorted(glob.glob(os.path.join(args.train_once, "networktopology*.dfc")))
        if not dl:
            print(f"trainer: no download*.dfc shards in {args.train_once}", file=sys.stderr)
            return 1
        for path in dl:
            session.send_download_shard(path)
        for path in topo:
            session.send_network_topology_shard(path)
        key = session.close_and_train()
        run_rec = service.runs[key]
        if run_rec.error:
            print(f"trainer: run failed: {run_rec.error}", file=sys.stderr)
            return 1
        for name, metrics in run_rec.metrics.items():
            print(
                f"trainer: {name}: mae={metrics.mae:.4f} mse={metrics.mse:.4f} "
                f"f1={metrics.f1:.3f} ({run_rec.download_rows} rows)"
            )
        for mid in run_rec.models:
            m = registry.get(mid)
            print(f"trainer: registered {m.name} v{m.version} ({m.type})")
        return 0

    # Serve mode: real ingest servers (trainer/rpcserver analog) — HTTP
    # chunked uploads, plus the gRPC Train client-stream when configured.
    from ..rpc import TrainerHTTPServer

    http_server = TrainerHTTPServer(
        service, host=cfg.server.host, port=cfg.server.port
    )
    http_server.serve()
    # Self-driving lifecycle plane (DESIGN.md §29): with a REST manager
    # attached, every ingested record also streams into the continuous
    # train→export→rollout loop — candidates register and walk
    # SHADOW→CANARY→ACTIVE with zero human steps (schedulers' rollout
    # reporters supply the evaluation evidence).
    lifecycle_daemon = None
    if (
        cfg.lifecycle.enable
        and manager_addr
        and not manager_addr.startswith("grpc://")
    ):
        from ..lifecycle import LifecycleConfig, LifecycleDaemon
        from ..rollout.client import RolloutRESTClient

        lc = cfg.lifecycle
        # No StateBackend here (that is the manager's): lifecycle
        # watermarks/lineage live in the daemon's in-memory store, so
        # the epoch cadence holds for the life of this process; the
        # manager-side rollout rows stay durable either way.
        lifecycle_daemon = LifecycleDaemon(
            registry,
            RolloutRESTClient(manager_addr, token=args.manager_token),
            config=LifecycleConfig(
                scheduler_id=args.scheduler_id,
                model_name=lc.model_name,
                regions=tuple(lc.regions),
                epoch_records=lc.epoch_records,
                max_steps_per_epoch=lc.max_steps_per_epoch,
                min_joined=lc.min_joined,
                arbitration_margin=lc.arbitration_margin,
                canary_percent=lc.canary_percent,
                interval_s=lc.interval_s,
                trainer_batch_size=lc.trainer_batch_size,
            ),
        )
        service.online_sink = lifecycle_daemon
        lifecycle_daemon.serve()
        print(
            f"trainer: lifecycle daemon on (epoch every {lc.epoch_records} "
            f"records, regions={list(lc.regions) or ['global only']})",
            flush=True,
        )
    elif cfg.lifecycle.enable:
        print(
            "trainer: lifecycle.enable set but no REST manager attached; "
            "lifecycle daemon not started",
            flush=True,
        )
    grpc_server = None
    if cfg.server.grpc_port >= 0:
        from ..rpc.grpc_transport import TrainerGRPCServer

        grpc_server = TrainerGRPCServer(
            service, host=cfg.server.host, port=cfg.server.grpc_port
        )
        grpc_server.serve()
    print(
        f"trainer: ingest on {http_server.url}"
        + (f" and grpc on {grpc_server.target}" if grpc_server else "")
        + f", staging in {cfg.data_dir} (ctrl-c to stop)",
        flush=True,
    )
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        http_server.stop()
        if grpc_server is not None:
            grpc_server.stop()
        return 0


if __name__ == "__main__":
    sys.exit(run())
