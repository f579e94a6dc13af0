"""configs[5] AS WRITTEN: the online 1B-edge graph trainer, for real.

The r3 soak (tools/soak_1b.py) trained 1B records against ONE static
graph snapshot.  This run is the *online* loop the config describes
(VERDICT r3 next-#1): BOTH record streams flow continuously —

- **downloads**: position-seeded edge batches whose ground-truth
  bandwidth reflects the cluster's CURRENT (drifting) load state;
- **topology**: per-epoch probe sweeps of the drifted cluster;

and every ``--refresh-every`` dispatches the trainer rebuilds the graph
snapshot from the topology window — ``build_neighbor_table`` +
``precompute_hop_features`` re-run mid-training, hop tables hot-swap,
optimizer/params/LR-position continue (trainer/online_graph.py).

Load drift happens at epoch boundaries (``SyntheticCluster.drift``,
seeded by epoch → a resumed run replays the identical world).  At every
boundary the tool logs val MAE on POST-drift edges twice: with the
STALE snapshot (pre-swap) and the FRESH one (post-swap) — the measured
evidence that the refresh loop chases the drift.

Kill/resume: --kill-after-dispatch exits hard after a checkpoint
(placed PAST a refresh boundary to prove resume across the swap);
--resume restores params/opt/stream position AND rebuilds the snapshot
from the checkpointed window; --hash-out proves the continuation
byte-identical to an uninterrupted run.

Usage (BENCHMARKS.md "online 1B" section records the measured runs):
  python tools/soak_online_1b.py --records 1e9 --ckpt-dir /tmp/og \\
      [--kill-after-dispatch 70] [--resume] [--hash-out H]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

BATCH = 131_072
SUPER = 64


def main() -> int:
    global BATCH, SUPER
    ap = argparse.ArgumentParser()
    ap.add_argument("--records", type=float, default=1e9)
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--refresh-every", type=int, default=30, help="dispatches per epoch")
    ap.add_argument("--ckpt-every", type=int, default=30, help="dispatches")
    ap.add_argument("--eval-every", type=int, default=15, help="dispatches")
    ap.add_argument("--kill-after-dispatch", type=int, default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--hash-out", default=None)
    ap.add_argument("--nodes", type=int, default=100_000)
    ap.add_argument("--hidden", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=BATCH)
    ap.add_argument("--super", dest="super_steps", type=int, default=SUPER)
    args = ap.parse_args()
    BATCH, SUPER = args.batch, args.super_steps

    t_wall0 = time.time()
    import jax

    from dragonfly2_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from dragonfly2_tpu.models.hop import HopConfig
    from dragonfly2_tpu.records.synthetic import SyntheticCluster
    from dragonfly2_tpu.trainer.online_graph import (
        OnlineGraphConfig,
        OnlineGraphTrainer,
        state_hash,
    )
    from dragonfly2_tpu.trainer.train import TrainConfig

    R = args.refresh_every
    n_dispatch_total = int(np.ceil(args.records / (BATCH * SUPER)))
    n_probe = args.nodes * 16  # one probe sweep per epoch ≈ table capacity

    # -- the (drifting) world, position-deterministic ------------------------
    cluster = SyntheticCluster(num_hosts=args.nodes, seed=0)

    def apply_drifts(up_to_epoch: int) -> None:
        """Replay epochs 1..up_to_epoch of load drift (seeded per epoch —
        a resumed process reconstructs the identical world state)."""
        for e in range(1, up_to_epoch + 1):
            cluster.drift(np.random.default_rng(77_000 + e))

    def probe_sweep(epoch: int):
        """Topology records for this epoch's world (prober → probed)."""
        rng = np.random.default_rng(88_000 + epoch)
        src = rng.integers(0, args.nodes, n_probe)
        dst = rng.integers(0, args.nodes, n_probe)
        keep = src != dst
        src, dst = src[keep], dst[keep]
        rtt = cluster._rtt_vec(src, dst, rng=rng) / 1e9
        return src, dst, rtt.astype(np.float32)

    # The producer runs AHEAD of the train loop (queue backpressure ≠
    # lockstep), so it generates against its OWN world replica, drifted at
    # its own generation position — sharing the main thread's cluster
    # would race its epoch-boundary drift and break position determinism.
    producer_cluster = SyntheticCluster(num_hosts=args.nodes, seed=0)

    def download_block(d: int):
        """Download records for dispatch d, against dispatch d's world."""
        rng = np.random.default_rng(10_000 + d)
        es = rng.integers(0, args.nodes, SUPER * BATCH).astype(np.int32)
        ed = (es + rng.integers(1, args.nodes, SUPER * BATCH).astype(np.int32)) % args.nodes
        y = np.log1p(
            producer_cluster._bandwidth_vec(es, ed, rng=rng)
        ).astype(np.float32)
        return es, ed, y

    def val_set(epoch: int):
        rng = np.random.default_rng(999_000 + epoch)
        es = rng.integers(0, args.nodes, 2 * BATCH).astype(np.int32)
        ed = (es + rng.integers(1, args.nodes, 2 * BATCH).astype(np.int32)) % args.nodes
        y = np.log1p(cluster._bandwidth_vec(es, ed, rng=rng)).astype(np.float32)
        return es, ed, y

    # -- trainer -------------------------------------------------------------
    t0 = time.time()
    src0, dst0, rtt0 = probe_sweep(0)
    cfg = OnlineGraphConfig(
        num_nodes=args.nodes,
        max_neighbors=16,
        batch_size=BATCH,
        super_steps=SUPER,
        refresh_every=0,   # the tool drives refreshes (stale/fresh eval around them)
        topo_window=n_probe,
        queue_capacity=2,
        model=HopConfig(hidden=args.hidden),
        train=TrainConfig(warmup_steps=100),
        total_steps_hint=n_dispatch_total * SUPER,
    )
    trainer = OnlineGraphTrainer(
        cfg,
        node_feats=cluster._host_feature_matrix(),
        topo_src=src0, topo_dst=dst0, topo_rtt=rtt0,
        checkpoint_dir=args.ckpt_dir,
    )
    print(f"soak-online: snapshot 0 built in {time.time() - t0:.1f}s "
          f"({args.nodes} nodes, {len(src0)} probes)", flush=True)

    start_dispatch = 0
    if args.resume:
        if not trainer.resume():
            print("soak-online: no checkpoint to resume", flush=True)
            return 1
        start_dispatch = trainer.dispatch
        # Rebuild the WORLD to match the restored stream position.
        apply_drifts(start_dispatch // R)
        print(f"soak-online: resumed at dispatch {start_dispatch} "
              f"(step {int(trainer.state.step)}, "
              f"snapshot {trainer.snapshot_idx})", flush=True)

    # -- producer: both streams, interleaved deterministically ---------------
    stop = threading.Event()

    def producer() -> None:
        for e in range(1, start_dispatch // R + 1):
            producer_cluster.drift(np.random.default_rng(77_000 + e))
        for d in range(start_dispatch, n_dispatch_total):
            if stop.is_set():
                return
            if d and d % R == 0 and d != start_dispatch:
                # Dispatch d is the first of epoch d//R: drift first.  On
                # resume the pre-loop already replayed start_dispatch//R
                # epochs — drifting again here would over-drift the world
                # and break byte-identity with the uninterrupted run.
                producer_cluster.drift(np.random.default_rng(77_000 + d // R))
            # Blocks on the queue (ingest backpressure).
            trainer.feed_downloads(*download_block(d))
        trainer.end_of_stream()

    threading.Thread(target=producer, daemon=True).start()

    # -- the run -------------------------------------------------------------
    curve = []
    refreshes = []
    t_train0 = time.time()
    d = start_dispatch
    while d < n_dispatch_total:
        ran = trainer.run(max_dispatches=1, idle_timeout=30.0)
        if ran == 0:
            break
        d += 1
        epoch = d // R
        if (d % args.eval_every == 0) or d == n_dispatch_total:
            # The boundary drift for epoch d//R runs BELOW — the world at
            # eval time is still dispatch d's epoch.
            es, ed, y = val_set((d - 1) // R)
            mae = trainer.eval_mae(es, ed, y)
            curve.append({"dispatch": d, "records": d * SUPER * BATCH,
                          "snapshot": trainer.snapshot_idx,
                          "val_log_mae": round(mae, 4)})
            print(f"soak-online: dispatch {d}/{n_dispatch_total} "
                  f"({d * SUPER * BATCH / 1e6:.0f}M records) "
                  f"snapshot={trainer.snapshot_idx} val_log_mae={mae:.4f}",
                  flush=True)
        if d % R == 0 and d < n_dispatch_total:
            # Epoch boundary: the world drifts; measure the model on the
            # NEW world with the STALE snapshot, refresh, measure FRESH.
            t_r0 = time.time()
            cluster.drift(np.random.default_rng(77_000 + epoch))
            es, ed, y = val_set(epoch)  # post-drift targets
            stale = trainer.eval_mae(es, ed, y)
            trainer.set_node_features(cluster._host_feature_matrix())
            trainer.feed_topology(*probe_sweep(epoch))
            digest = trainer.refresh_snapshot()
            fresh = trainer.eval_mae(es, ed, y)
            refreshes.append({
                "dispatch": d, "epoch": epoch,
                "stale_mae": round(stale, 4), "fresh_mae": round(fresh, 4),
                "refresh_s": round(time.time() - t_r0, 2),
                "hop_digest": digest[:12] if digest else None,
            })
            print(f"soak-online: REFRESH at dispatch {d}: "
                  f"stale={stale:.4f} fresh={fresh:.4f} "
                  f"({refreshes[-1]['refresh_s']}s)", flush=True)
        saved = False
        if d % args.ckpt_every == 0 or d == n_dispatch_total:
            trainer.checkpoint()
            saved = True
        if args.kill_after_dispatch is not None and d >= args.kill_after_dispatch:
            if not saved:
                trainer.checkpoint()
            stop.set()
            if args.hash_out:
                with open(args.hash_out + ".at_kill", "w") as f:
                    f.write(state_hash(trainer.state) + "\n")
            print(f"soak-online: KILLING after dispatch {d} "
                  f"(checkpoint written, snapshot {trainer.snapshot_idx})",
                  flush=True)
            os._exit(137)

    jax.block_until_ready(trainer.state.params)
    train_s = time.time() - t_train0
    wall_s = time.time() - t_wall0
    records_done = (d - start_dispatch) * SUPER * BATCH

    if args.hash_out:
        digest = state_hash(trainer.state)
        with open(args.hash_out, "w") as f:
            f.write(digest + "\n")
        print(f"soak-online: state sha256 {digest[:16]}…", flush=True)

    print(json.dumps({
        "records_this_run": records_done,
        "dispatches": d - start_dispatch,
        "snapshots": trainer.snapshot_idx,
        "train_s": round(train_s, 1),
        "wall_s": round(wall_s, 1),
        "records_per_s_incl_refresh": round(records_done / train_s, 1),
        "refreshes": refreshes,
        "val_curve": curve,
        "resumed": args.resume,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
