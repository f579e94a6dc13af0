"""Width-quality ablation: does a ≥30%-MFU hop-ranker width hold val MAE?

VERDICT r2 weak-#1: the mfu_wide.py sweep showed hidden 512/1024 hitting
27/53% MFU but carried **no quality numbers and ran with dropout off** —
so the ≥30%-MFU north-star bar (BASELINE.json) stayed unmet.  This tool
closes that gap: the exact config[2] ablation workload
(tools/ablate_rankers.py — 100k-node probe graph, 2M download edges,
log1p-bandwidth targets, identical split/seed) trained at each width
with the PRODUCTION dropout (HopConfig default 0.1) and the production
train loop (train_hop_ranker).

Promotion rule (VERDICT r2 next-#1 done-condition): a width whose val
log-MAE ≤ the width-128 flagship's becomes the flagship bench config.

Usage:
  PYTHONPATH=. python tools/ablate_width.py [widths...]
Prints one JSON line per width.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def main() -> None:
    import jax

    from dragonfly2_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from dragonfly2_tpu.models import build_neighbor_table
    from dragonfly2_tpu.models.hop import HopConfig
    from dragonfly2_tpu.records.synthetic import SyntheticCluster
    from dragonfly2_tpu.trainer.train import TrainConfig, train_hop_ranker

    widths = [int(a) for a in sys.argv[1:] if a.isdigit()] or [128, 512, 1024]
    on_tpu = jax.devices()[0].platform != "cpu"
    n_nodes = 100_000 if on_tpu else 2_000
    n_edges = 2_000_000 if on_tpu else 40_000
    epochs = 60 if on_tpu else 8

    print(
        f"# workload: {n_nodes} nodes, {n_edges} edges, {epochs} epochs, "
        f"widths {widths}", file=sys.stderr, flush=True,
    )
    cluster = SyntheticCluster(num_hosts=n_nodes, seed=0)
    src, dst, rtt = cluster.probe_edges(density=16 / max(n_nodes - 1, 1), seed=0)
    table = build_neighbor_table(n_nodes, src, dst, rtt / 1e9, max_neighbors=16)
    nf = cluster._host_feature_matrix()

    rng = np.random.default_rng(0)
    es = rng.integers(0, n_nodes, n_edges).astype(np.int32)
    ed = (es + rng.integers(1, n_nodes, n_edges).astype(np.int32)) % n_nodes
    y = np.log1p(cluster._bandwidth_vec(es, ed)).astype(np.float32)
    cfg = TrainConfig(epochs=epochs)

    for hidden in widths:
        mcfg = HopConfig(hidden=hidden)  # production dropout (0.1) stays ON
        t0 = time.time()
        _, m, hist = train_hop_ranker(
            nf, table, es, ed, y, model_config=mcfg, config=cfg,
            batch_size=131_072,
        )
        print(json.dumps({
            "model": f"hop-h{hidden}",
            "hidden": hidden,
            "dropout": mcfg.dropout,
            "val_log_mae": round(m.mae, 4),
            "f1": round(m.f1, 4),
            "wall_s": round(time.time() - t0, 1),
            "records_per_sec": round(hist[-1]["records_per_sec"], 1) if hist else None,
        }), flush=True)


if __name__ == "__main__":
    main()
