"""Multi-tenant QoS isolation benchmark (DESIGN.md §26).

Drives ``sim/qos.py``'s overload drill — a measured tenant-A workload
(announce loop + real downloads off a seed daemon) against a tenant-B
announce+download flood — in INTERLEAVED rounds (bench_sched
discipline: GC quiesced, identical config per round, arms inside one
round share one box state):

- ``baseline``  — tenant A alone;
- ``unshaped``  — the burst with tenant-blind admission and no caps
                  (documents the baseline interference);
- ``shaped``    — the burst with the QoS plane live (background class,
                  announce-rate cap, upload-bandwidth cap, per-tenant
                  accounting + noisy-first shedding).

Headline: **isolation_score = 100 − max(shaped movement of tenant A's
announce p99 and download TTLB, in %, floored at 0)** over the best
round — ≥ 90 means the <10% isolation bar held.  Regression-guarded
over ``BENCH_QOS_r*.json`` (``tools/regression_guard.py``'s 20% tripwire).
The 1-CPU
caveats (BENCHMARKS.md): per-round variance is real (±10-20% on these
µs/ms-scale signals — the announce p99 can move NEGATIVE under load
because the flood keeps the core hot), which is why rounds are
interleaved and the best round is the headline, same as bench_swarm.

Usage: PYTHONPATH=/root/repo python tools/bench_qos.py
       [--rounds 3] [--announces 1200] [--downloads 10]
       [--pieces 8] [--piece-size 65536] [--b-threads 2] [--seed 7]
       [--smoke]   # tiny drill: the tier-1 JSON-schema gate
"""

from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

SCHEMA_KEYS = (
    "ok",
    "metric",
    "value",
    "config",
    "rounds",
    "best",
    "movement",
    "arms",
)

ARM_KEYS = (
    "a_announce_p99_ms",
    "a_ttlb_ms",
    "b_offered",
    "b_sheds",
    "b_throttled",
    "a_downloads_ok",
)


def last_good_qos(repo_dir: Optional[str] = None) -> dict:
    """Most recent BENCH_QOS_r*.json with a parsed isolation headline —
    the QoS regression bar (tools/regression_guard.py)."""
    repo_dir = repo_dir or str(Path(__file__).resolve().parents[1])
    best: dict = {}
    for path in glob.glob(os.path.join(repo_dir, "BENCH_QOS_r*.json")):
        m = re.search(r"BENCH_QOS_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            continue
        value = data.get("value")
        if value is None:
            continue
        n = int(m.group(1))
        if not best or n > best["round"]:
            best = {
                "round": n,
                "value": float(value),
                "file": os.path.basename(path),
            }
    return best


def _isolation_score(movement: Dict[str, float]) -> float:
    """100 − the worst shaped movement (announce p99 / TTLB), floored at
    0 from below (a negative movement is no interference, not credit)."""
    worst = max(
        0.0,
        float(movement["shaped_announce_p99_pct"]),
        float(movement["shaped_ttlb_pct"]),
    )
    return round(max(0.0, 100.0 - worst), 2)


def run(args) -> Dict[str, object]:
    from dragonfly2_tpu.sim.qos import QoSDrillConfig, run_isolation_drill

    cfg = QoSDrillConfig(
        a_announces=args.announces,
        a_downloads=args.downloads,
        pieces_per_task=args.pieces,
        piece_size=args.piece_size,
        b_threads=args.b_threads,
        seed=args.seed,
    )
    rounds: List[Dict[str, object]] = []
    gc.collect()
    gc.disable()
    try:
        for _ in range(max(1, args.rounds)):
            rounds.append(run_isolation_drill(cfg))
    finally:
        gc.enable()
    scored = [
        (_isolation_score(r["movement"]), i) for i, r in enumerate(rounds)
    ]
    best_score, best_i = max(scored)
    best = rounds[best_i]
    # Every round must prove the flood actually ran and the shaped arm
    # actually shed/capped it — an idle flood is a vacuous isolation.
    for r in rounds:
        if r["unshaped"]["b_offered"] == 0:
            raise RuntimeError("tenant-B flood never ran in an unshaped arm")
        shaped = r["shaped"]
        if shaped["b_sheds"] + shaped["b_throttled"] == 0:
            raise RuntimeError("shaped arm never shed or capped the flood")
        if shaped["a_downloads_ok"] != args.downloads:
            raise RuntimeError(
                "tenant-A downloads failed under the shaped burst: "
                f"{shaped['a_downloads_ok']}/{args.downloads}"
            )
    return {
        "ok": True,
        "metric": "qos_isolation_score",
        "value": best_score,
        "config": {
            "rounds": args.rounds,
            "a_announces": cfg.a_announces,
            "a_downloads": cfg.a_downloads,
            "pieces_per_task": cfg.pieces_per_task,
            "piece_size": cfg.piece_size,
            "b_threads": cfg.b_threads,
            "b_announce_qps": cfg.b_announce_qps,
            "b_upload_rate": cfg.b_upload_rate,
            "seed": cfg.seed,
        },
        "rounds": [r["movement"] for r in rounds],
        "best": best["movement"],
        "movement": best["movement"],
        "arms": {
            "baseline": best["baseline"],
            "unshaped": best["unshaped"],
            "shaped": best["shaped"],
        },
        "unshaped_interference": {
            "announce_p99_pct": best["movement"]["unshaped_announce_p99_pct"],
            "ttlb_pct": best["movement"]["unshaped_ttlb_pct"],
        },
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--announces", type=int, default=1200)
    p.add_argument("--downloads", type=int, default=10)
    p.add_argument("--pieces", type=int, default=8)
    p.add_argument("--piece-size", type=int, default=64 * 1024)
    p.add_argument("--b-threads", type=int, default=2)
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--smoke", action="store_true",
                   help="tiny drill: the tier-1 JSON-schema gate")
    args = p.parse_args(argv)
    if args.smoke:
        args.rounds, args.announces, args.downloads = 1, 200, 3
        args.pieces, args.piece_size = 4, 16 * 1024
    try:
        out = run(args)
        missing = [k for k in SCHEMA_KEYS if k not in out]
        for arm, stats in out["arms"].items():
            missing += [f"{arm}.{k}" for k in ARM_KEYS if k not in stats]
        if missing:
            raise RuntimeError(f"schema keys missing: {missing}")
        from tools.regression_guard import apply_regression_guard

        guard = {"value": out["value"]}
        apply_regression_guard(guard, last_good_qos())
        out["last_good"] = guard.get("last_good", {})
        if "regression_warning" in guard:
            out["regression_warning"] = guard["regression_warning"]
    except Exception as exc:  # noqa: BLE001 — one parseable line, never a traceback
        print(json.dumps({
            "ok": False,
            "metric": "qos_isolation_score",
            "error": f"{type(exc).__name__}: {exc}"[:300],
        }))
        return 1
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
