"""The 1B-record north-star run, FOR REAL (VERDICT r2 next-#2).

BASELINE north star: "train the peer-bandwidth GNN on 1B download
records over a 100k-node peer graph ... in ≤10 min at ≥30% MFU".  This
tool runs it end to end on the chip, not by extrapolation:

- Phase 0 (counted in wall time): 100k-node probe graph build + hop-
  feature precompute for the flagship hop ranker (hidden 1024).
- Ingest: a producer thread generates download-record superbatches
  (HOST-side, bounded queue, backpressure — the streaming-trainer
  boundary) that reach the device as [K, B] arrays; targets normalize
  with log1p in the path.
- Train: one jitted lax.scan steps K batches per dispatch; a held-out
  edge set scores val log-MAE periodically (the quality curve).
- Checkpoint/resume: orbax snapshots (params + opt state + step +
  stream position); --kill-after-dispatch exits HARD right after a
  snapshot (crash simulation), --resume restores and continues the
  deterministic stream from the recorded position.  --hash-out writes a
  sha256 over the final params+opt_state bytes so a kill+resume run can
  be proven BYTE-IDENTICAL to an uninterrupted one.

Usage (see BENCHMARKS.md "1B-record north-star run" for the measured
invocations):
  python tools/soak_1b.py --records 1e9 --ckpt-dir /tmp/soak \\
      [--kill-after-dispatch 60] [--resume] [--hash-out H]
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import sys
import threading
import time

import numpy as np

BATCH = 131_072
SUPER = 64  # steps per dispatch: 8.39M records per host→device transfer


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--records", type=float, default=1e9)
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--ckpt-every", type=int, default=30, help="dispatches")
    ap.add_argument("--eval-every", type=int, default=15, help="dispatches")
    ap.add_argument("--kill-after-dispatch", type=int, default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--hash-out", default=None)
    ap.add_argument("--hash-restored", default=None,
                    help="with --resume: hash the state right after "
                         "restore and exit (roundtrip diagnostics)")
    ap.add_argument("--host-roundtrip-at", type=int, default=None,
                    help="diagnostics: after dispatch N, pull the state "
                         "to host numpy and push it back (no checkpoint)")
    ap.add_argument("--nodes", type=int, default=100_000)
    ap.add_argument("--hidden", type=int, default=1024)
    args = ap.parse_args()
    if args.hash_restored and not args.resume:
        ap.error("--hash-restored requires --resume")

    t_wall0 = time.time()
    import jax

    from dragonfly2_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax.numpy as jnp
    import orbax.checkpoint as ocp

    from dragonfly2_tpu.models import (
        HopConfig,
        HopRanker,
        build_neighbor_table,
        precompute_hop_features,
    )
    from dragonfly2_tpu.records.synthetic import SyntheticCluster
    from dragonfly2_tpu.trainer.online_graph import state_hash
    from dragonfly2_tpu.trainer.train import (
        TrainConfig,
        TrainState,
        _graph_train_step,
        _make_optimizer,
    )

    # -- phase 0: graph + hop features (counted) ----------------------------
    t0 = time.time()
    cluster = SyntheticCluster(num_hosts=args.nodes, seed=0)
    src, dst, rtt = cluster.probe_edges(
        density=16 / max(args.nodes - 1, 1), seed=0
    )
    table = build_neighbor_table(
        args.nodes, src, dst, rtt / 1e9, max_neighbors=16
    )
    node_feats = jnp.asarray(cluster._host_feature_matrix())
    mcfg = HopConfig(hidden=args.hidden)
    hop_feats = jax.jit(
        lambda nf, t: precompute_hop_features(nf, t, hops=mcfg.hops)
    )(node_feats, table)
    hop_feats.block_until_ready()
    precompute_s = time.time() - t0
    print(f"soak: hop-feature precompute {precompute_s:.1f}s "
          f"({args.nodes} nodes)", flush=True)

    # -- model / optimizer ---------------------------------------------------
    n_dispatch_total = int(np.ceil(args.records / (BATCH * SUPER)))
    model = HopRanker(mcfg)
    rng0 = np.random.default_rng(123)
    init_src = jnp.asarray(rng0.integers(0, args.nodes, 2), jnp.int32)
    params = model.init(
        jax.random.PRNGKey(0), hop_feats, table, init_src, init_src
    )["params"]
    cfg = TrainConfig(warmup_steps=100)
    tx = _make_optimizer(cfg, n_dispatch_total * SUPER // max(cfg.epochs, 1))
    state = TrainState.create(
        apply_fn=model.apply, params=params, tx=tx,
        dropout_rng=jax.random.PRNGKey(1),
    )

    # -- deterministic stream (ingest) ---------------------------------------
    def edge_targets(rng, es, ed):
        """Bandwidth targets with the measurement noise drawn from the
        CALLER's rng — the cluster's shared stateful generator would make
        the stream depend on how many draws happened before (a resumed
        process would regenerate a DIFFERENT continuation and break
        byte-identity).  The noise model stays in ONE place
        (synthetic.py _bandwidth_vec)."""
        return np.log1p(cluster._bandwidth_vec(es, ed, rng=rng)).astype(np.float32)

    def make_superbatch(d: int):
        """Download records for dispatch d — seeded by the STREAM position
        so a resumed run regenerates the identical continuation."""
        rng = np.random.default_rng(10_000 + d)
        es = rng.integers(0, args.nodes, SUPER * BATCH).astype(np.int32)
        ed = (es + rng.integers(1, args.nodes, SUPER * BATCH).astype(np.int32)) % args.nodes
        y = edge_targets(rng, es, ed)
        return (
            es.reshape(SUPER, BATCH), ed.reshape(SUPER, BATCH),
            y.reshape(SUPER, BATCH),
        )

    # Held-out quality set (disjoint seed from every dispatch).
    vrng = np.random.default_rng(999_999)
    v_es = vrng.integers(0, args.nodes, 2 * BATCH).astype(np.int32)
    v_ed = (v_es + vrng.integers(1, args.nodes, 2 * BATCH).astype(np.int32)) % args.nodes
    v_y = edge_targets(vrng, v_es, v_ed)
    v_es, v_ed, v_y = (jnp.asarray(a) for a in (v_es, v_ed, v_y))

    @jax.jit
    def train_dispatch(s, es, ed, y):
        def body(carry, xs):
            b_es, b_ed, b_y = xs
            new_s, loss = _graph_train_step(
                carry, hop_feats, table, b_es, b_ed, b_y, None
            )
            return new_s, loss

        s, losses = jax.lax.scan(body, s, (es, ed, y))
        return s, losses.mean()

    @jax.jit
    def val_mae(s):
        pred = s.apply_fn(
            {"params": s.params}, hop_feats, table, v_es, v_ed, train=False
        )
        return jnp.abs(pred - v_y).mean()

    # -- checkpoint / resume -------------------------------------------------
    ckpt_path = os.path.join(os.path.abspath(args.ckpt_dir), "soak")

    def save(dispatch: int) -> None:
        ckptr = ocp.StandardCheckpointer()
        ckptr.save(
            ckpt_path,
            {
                "params": state.params, "opt_state": state.opt_state,
                "step": int(state.step), "dispatch": dispatch,
                "dropout_rng": state.dropout_rng,
            },
            force=True,
        )
        ckptr.wait_until_finished()

    start_dispatch = 0
    if args.resume:
        ckptr = ocp.StandardCheckpointer()
        abstract = {
            "params": state.params, "opt_state": state.opt_state,
            "step": 0, "dispatch": 0, "dropout_rng": state.dropout_rng,
        }
        restored = ckptr.restore(ckpt_path, abstract)
        # step must restore as a STRONG int32 device scalar — the mid-run
        # state carries one, and a weak-typed Python int would compile a
        # DIFFERENT XLA program whose float-reduction order diverges from
        # the uninterrupted run (measured: byte-identity holds only with
        # matching avals).
        state = state.replace(
            params=restored["params"], opt_state=restored["opt_state"],
            step=jnp.asarray(restored["step"], jnp.int32),
            dropout_rng=jnp.asarray(restored["dropout_rng"], jnp.uint32),
        )
        start_dispatch = int(restored["dispatch"])
        print(f"soak: resumed at dispatch {start_dispatch} "
              f"(step {int(state.step)})", flush=True)
        if args.hash_restored:
            with open(args.hash_restored, "w") as f:
                f.write(state_hash(state) + "\n")
            print("soak: restored-state hash written; exiting", flush=True)
            return 0

    # -- producer (bounded queue = ingest backpressure) ----------------------
    feed: "queue.Queue" = queue.Queue(maxsize=2)

    def producer() -> None:
        for d in range(start_dispatch, n_dispatch_total):
            feed.put((d, make_superbatch(d)))
        feed.put(None)

    threading.Thread(target=producer, daemon=True).start()

    # -- the soak ------------------------------------------------------------
    curve = []
    t_train0 = time.time()
    while True:
        item = feed.get()
        if item is None:
            break
        d, (es, ed, y) = item
        state, loss = train_dispatch(
            state, jnp.asarray(es), jnp.asarray(ed), jnp.asarray(y)
        )
        if (d + 1) % args.eval_every == 0 or d == n_dispatch_total - 1:
            mae = float(val_mae(state))
            records = (d + 1) * SUPER * BATCH
            curve.append({"dispatch": d + 1, "records": records,
                          "val_log_mae": round(mae, 4)})
            print(f"soak: dispatch {d + 1}/{n_dispatch_total} "
                  f"({records / 1e6:.0f}M records) val_log_mae={mae:.4f} "
                  f"loss={float(loss):.4f}", flush=True)
        if args.host_roundtrip_at is not None and d + 1 == args.host_roundtrip_at:
            state = jax.tree_util.tree_map(
                lambda leaf: jnp.asarray(np.asarray(leaf))
                if hasattr(leaf, "dtype") else leaf,
                state,
            )
            print(f"soak: host roundtrip after dispatch {d + 1}", flush=True)
        saved = (d + 1) % args.ckpt_every == 0 or d == n_dispatch_total - 1
        if saved:
            save(d + 1)
        if args.kill_after_dispatch is not None and d + 1 >= args.kill_after_dispatch:
            if not saved:  # the periodic branch may have JUST written it
                save(d + 1)
            if args.hash_out:
                with open(args.hash_out + ".at_kill", "w") as f:
                    f.write(state_hash(state) + "\n")
            print(f"soak: KILLING after dispatch {d + 1} "
                  f"(checkpoint written)", flush=True)
            os._exit(137)

    jax.block_until_ready(state.params)
    train_s = time.time() - t_train0
    wall_s = time.time() - t_wall0
    records_done = (n_dispatch_total - start_dispatch) * SUPER * BATCH

    if args.hash_out:
        digest = state_hash(state)
        with open(args.hash_out, "w") as f:
            f.write(digest + "\n")
        print(f"soak: state sha256 {digest[:16]}…", flush=True)

    print(json.dumps({
        "records_this_run": records_done,
        "dispatches": n_dispatch_total - start_dispatch,
        "precompute_s": round(precompute_s, 1),
        "train_s": round(train_s, 1),
        "wall_s": round(wall_s, 1),
        "records_per_s": round(records_done / train_s, 1),
        "val_curve": curve,
        "resumed": args.resume,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
