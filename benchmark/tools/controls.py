"""The control and the planted faults of a cell, read at the cell's own
size: the plain reference put in the program's place, computed (a) with
every Dense in fp8, the nearest precision below the bfloat16 the
configuration states, and (b) with half of each batch left out and the
mean taken over the rest.  Each is compared with the float32 reference
exactly as a run compares the program.  Prints one JSON line a seed; the
limits of a cell's file lie under the smallest of these readings.

    python3 benchmark/tools/controls.py --workload <cell> --seeds 1 2 3

Not part of a benchmark run.  Needs the chip only for the size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def readings(cell: dict, config: dict, seed: int, variants, devices) -> dict:
    import jax

    from benchmark import check, run, traffic

    reference = run.load_module("reference", cell["config"])
    driver = run.load_module("drivers", cell["driver"])
    inputs = traffic.make_inputs(cell["traffic"], seed)
    ctx = run.Ctx(cell, config, run.load_module("configs", cell["config"]), inputs, seed, devices)
    ref_in = driver.reference_inputs(ctx)
    limits = {k: v for k, v in cell["limits"].items() if k != "repeat_gap"}
    with jax.default_matmul_precision("highest"):
        ref = reference.first_steps(config["model"], config["train"], ref_in)
        out = {"seed": seed, "mean_residual": ref["mean_residual"]}
        for variant in variants:
            got = reference.first_steps(config["model"], config["train"], ref_in, variant)
            if ref_in.get("mean_loss_only"):
                got["losses"] = [sum(got["losses"]) / len(got["losses"])]
            out[variant] = {r["name"]: r["value"] for r in check.compare(got, ref, limits)}
            if os.environ.get("BENCH_BY_LEAF"):
                out[variant + "_by_leaf"] = check.by_leaf(got, ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", default=["fp8", "half_batch"])
    args = ap.parse_args(argv)

    import jax

    from benchmark import run

    cell, config = run.load_cell_files(args.workload)
    for seed in args.seeds:
        print(json.dumps(readings(cell, config, seed, args.variants, jax.devices())), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
