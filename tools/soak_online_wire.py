"""Online graph training fed through the REAL wire, on the chip.

The 1B online soak (tools/soak_online_1b.py) proves the training loop
at scale with in-process feeds; tools/bench_wire_ingest.py proves the
Train stream moves bytes faster than training consumes them.  This tool
composes the two END TO END with no shortcuts in between:

  producer ──HTTP Train stream (DFC1 chunks)──► TrainerService
      (online_sink) ──StreamingRowDecoder──► WireIngestAdapter
      ──bounded queues──► OnlineGraphTrainer (TPU) ── snapshot refreshes
      from WIRE-fed topology shards

Both record types ride the wire: download chunks continuously, a probe
sweep per epoch.  Every ``--refresh-every`` dispatches the trainer
rebuilds its graph from the wire-fed window (hop tables hot-swap,
optimizer untouched).  The sustained rate is HONESTLY producer-bound
(~1.5M rows/s of numpy generation; wire ~4M rec/s and the train step
~4.8M rec/s are each measured faster in BENCHMARKS.md) — the point is
that the composed pipeline holds the north-star consumption rate
(1.3M records/s) with every hop real.

Usage:
  PYTHONPATH=. python tools/soak_online_wire.py \\
      [--records 2e8] [--nodes 100000] [--hidden 1024]
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time

import numpy as np


def _producer_proc(
    base_url: str, session_id: str, nodes: int, block_rows: int,
    total: int, rows_per_epoch: int, idx: int = 0, n_producers: int = 1,
) -> None:
    """Runs in its own PROCESS: generate the drifting world's records and
    stream both dataset kinds to the trainer's wire."""
    import urllib.request

    from dragonfly2_tpu.records.columnar import ColumnarHeader, _encode_header
    from dragonfly2_tpu.records.features import DOWNLOAD_COLUMNS, TOPO_COLUMNS
    from dragonfly2_tpu.records.synthetic import SyntheticCluster

    cluster = SyntheticCluster(num_hosts=nodes, seed=0)
    buckets = cluster._bucket_table()
    header = _encode_header(ColumnarHeader(columns=DOWNLOAD_COLUMNS))
    seqs: dict = {}

    def post(kind: str, name: str, payload: bytes) -> None:
        seq = seqs.get(name, 0)
        req = urllib.request.Request(
            f"{base_url}/train/shard?session={session_id}&kind={kind}"
            f"&name={name}&seq={seq}",
            data=payload, method="POST",
        )
        urllib.request.urlopen(req, timeout=600).close()
        seqs[name] = seq + 1

    def probe_shard(epoch: int) -> bytes:
        rng = np.random.default_rng(88_000 + epoch)
        n = nodes * 16
        src = rng.integers(0, nodes, n)
        dst = rng.integers(0, nodes, n)
        keep = src != dst
        src, dst = src[keep], dst[keep]
        rows = np.zeros((len(src), len(TOPO_COLUMNS)), np.float32)
        rows[:, 0] = buckets[src]
        rows[:, 1] = buckets[dst]
        rows[:, 2] = (cluster._rtt_vec(src, dst, rng=rng) / 1e9).astype(
            np.float32
        )
        return _encode_header(
            ColumnarHeader(columns=TOPO_COLUMNS)
        ) + rows.tobytes()

    # Producer i takes global blocks i, i+P, i+2P, … — many producers,
    # one stream (the deployment shape: several schedulers upload to one
    # trainer).  Only producer 0 ships the topology sweeps.
    epoch = -1
    n_blocks = (total + block_rows - 1) // block_rows
    for g in range(idx, n_blocks, n_producers):
        offset = g * block_rows
        e = offset // rows_per_epoch
        if e != epoch:
            while epoch < e:
                epoch += 1
                if epoch > 0:
                    cluster.drift(np.random.default_rng(77_000 + epoch))
            if idx == 0:
                post("networktopology", f"topo-{epoch}", probe_shard(epoch))
        n = min(block_rows, total - offset)
        rows = cluster.generate_feature_rows(n, seed=10_000 + g)
        name = f"dl-{epoch}-p{idx}"
        payload = (header if seqs.get(name, 0) == 0 else b"")
        post("download", name, payload + rows.tobytes())


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--records", type=float, default=2e8)
    ap.add_argument("--nodes", type=int, default=100_000)
    ap.add_argument("--hidden", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=131_072)
    ap.add_argument("--super", dest="super_steps", type=int, default=8)
    ap.add_argument("--refresh-every", type=int, default=0,
                    help="dispatches per snapshot refresh (0 = auto: 3 swaps)")
    ap.add_argument("--block-rows", type=int, default=1_000_000)
    ap.add_argument("--producers", type=int, default=4)
    ap.add_argument("--stage-dir", default="/dev/shm",
                    help="staging parent (tmpfs isolates the sandbox disk)")
    args = ap.parse_args()

    import tempfile

    from dragonfly2_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    from dragonfly2_tpu.models.hop import HopConfig
    from dragonfly2_tpu.records.columnar import _encode_header, ColumnarHeader
    from dragonfly2_tpu.records.features import DOWNLOAD_COLUMNS, TOPO_COLUMNS
    from dragonfly2_tpu.records.synthetic import SyntheticCluster
    from dragonfly2_tpu.rpc.trainer_transport import (
        RemoteTrainer,
        TrainerHTTPServer,
    )
    from dragonfly2_tpu.trainer.online_graph import (
        OnlineGraphConfig,
        OnlineGraphTrainer,
    )
    from dragonfly2_tpu.trainer.service import TrainerService
    from dragonfly2_tpu.trainer.train import TrainConfig

    t_wall0 = time.time()
    rows_per_dispatch = args.batch * args.super_steps
    n_dispatch = int(np.ceil(args.records / rows_per_dispatch))
    R = args.refresh_every or max(n_dispatch // 4, 1)

    # Trainer on the chip, fed ONLY by the wire.
    cfg = OnlineGraphConfig(
        num_nodes=args.nodes,
        max_neighbors=16,
        batch_size=args.batch,
        super_steps=args.super_steps,
        refresh_every=R,
        topo_window=args.nodes * 16,
        queue_capacity=4,
        model=HopConfig(hidden=args.hidden),
        train=TrainConfig(warmup_steps=50),
        total_steps_hint=n_dispatch * args.super_steps,
    )
    trainer = OnlineGraphTrainer(
        cfg,
        node_feats=np.zeros((args.nodes, 12), np.float32),
        topo_src=np.zeros(0, np.int32), topo_dst=np.zeros(0, np.int32),
        topo_rtt=np.zeros(0, np.float32),
    )
    adapter = trainer.make_wire_adapter()
    stage = tempfile.mkdtemp(prefix="wire-soak-", dir=args.stage_dir)
    service = TrainerService(data_dir=stage, online_sink=adapter)
    service._run_training = lambda run, session: run.done.set()
    server = TrainerHTTPServer(service)
    server.serve()
    client = RemoteTrainer(server.url, timeout=600)
    session = client.open_train_stream(
        ip="10.9.9.9", hostname="wire-soak", scheduler_id="soak"
    )


    # The producer runs in its OWN process (the deployment shape: the
    # scheduler generating/uploading datasets is never the trainer's
    # process) — HTTP is already the boundary, so only the server URL,
    # session id, and scale parameters cross.
    import multiprocessing as mp

    # The producers start after this process holds the chip: they must
    # stay numpy-only — a child that touches JAX would fail or hang.
    ctx = mp.get_context("spawn")
    prods = [
        ctx.Process(
            target=_producer_proc,
            args=(server.url, session._session_id, args.nodes,
                  args.block_rows, int(n_dispatch * rows_per_dispatch),
                  R * rows_per_dispatch, i, args.producers),
            daemon=True,
        )
        for i in range(args.producers)
    ]
    for pr in prods:
        pr.start()

    def watch_producer() -> None:
        for pr in prods:
            pr.join()
        trainer.end_of_stream()

    threading.Thread(target=watch_producer, daemon=True).start()

    # Snapshot 0 comes OFF THE WIRE: wait for the producer's first probe
    # sweep to land, then build the first real graph before training.
    deadline = time.time() + 120
    while trainer._fed_since_swap == 0 and time.time() < deadline:
        time.sleep(0.1)
    assert trainer.refresh_snapshot() is not None, "no wire topology arrived"
    print(f"wire-soak: snapshot from wire topology "
          f"({len(trainer._window[0])} probe edges)", flush=True)

    t0 = time.time()
    d = 0
    last = t0
    while d < n_dispatch:
        ran = trainer.run(max_dispatches=1, idle_timeout=60.0)
        if ran == 0:
            break
        d += 1
        now = time.time()
        if now - last > 15 or d == n_dispatch:
            rate = trainer.records_seen / (now - t0)
            fed = sum(service._online_fed.values())
            print(f"wire-soak: dispatch {d}/{n_dispatch} "
                  f"({trainer.records_seen / 1e6:.0f}M trained, "
                  f"{fed / 1e6:.0f}M rows off the wire, "
                  f"snapshot={trainer.snapshot_idx}) "
                  f"sustained={rate / 1e6:.2f}M rec/s", flush=True)
            last = now
    train_s = time.time() - t0
    for pr in prods:
        if pr.is_alive():
            pr.terminate()
    server.stop()
    final_overflow = adapter.overflow_edges
    trainer.close()  # release the native ingest engine's buffers

    import shutil

    shutil.rmtree(stage, ignore_errors=True)
    fed = sum(service._online_fed.values())
    row_bytes = 4 * len(DOWNLOAD_COLUMNS)
    print(json.dumps({
        "records_trained": trainer.records_seen,
        "rows_off_the_wire": fed,
        "dispatches": d,
        "snapshots": trainer.snapshot_idx,
        "overflow_edges": final_overflow,
        "train_s": round(train_s, 1),
        "wall_s": round(time.time() - t_wall0, 1),
        "records_per_s_sustained": round(trainer.records_seen / train_s, 1),
        "payload_MBps": round(trainer.records_seen * row_bytes / train_s / 1e6, 1),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
