"""A kept trace of a stream-driver cell by the step's own scopes.

    python3 benchmark/run.py --workload <cell> ... --trace 1 --keep-trace <dir>
    python3 benchmark/tools/stream_trace.py <dir>/<file>.xplane.pb --workload <cell>

The table PERF.md section 5 keeps for such a cell: own device time of the
first device by scope (``stream/gdn/scan``, ``stream/moe/experts``, ...,
``loss``, ``optimizer``), forward and backward apart, and the operations
that took most with the scope of each.  The compiled dispatch is built
here at the cell's sizes, from the persistent cache where the run left it
(``tools/program_trace.py`` does the same for the online driver's cells and
prints the host's spans and idle gaps, which are the same for both).
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Dict, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def program_text(workload: str) -> str:
    import numpy as np

    from benchmark import run
    from dragonfly2_tpu.trainer.online_graph import OnlineGraphConfig, OnlineGraphTrainer
    from dragonfly2_tpu.utils.compile_cache import enable_compile_cache

    cell, config = run.load_cell_files(workload)
    if cell["driver"] != "stream":
        raise SystemExit(f"stream_trace: {workload} is a cell of the {cell['driver']} driver")
    enable_compile_cache()
    p, graph, model = cell["driver_params"], config["graph"], config["model"]
    model["positions"] = int(p["batch_size"]) // int(p["rows"])
    nothing = np.zeros(0, np.int32)
    trainer = OnlineGraphTrainer(
        OnlineGraphConfig(
            num_nodes=graph["num_nodes"], max_neighbors=graph["max_neighbors"],
            batch_size=int(p["batch_size"]), super_steps=int(p["super_steps"]),
            queue_capacity=int(p["queue_capacity"]),
            model=run.load_module("configs", cell["config"]).model_config(model),
        ),
        node_feats=np.zeros((graph["num_nodes"], graph["node_feature_dim"]), np.float32),
        topo_src=nothing, topo_dst=nothing, topo_rtt=nothing.astype(np.float32),
    )
    try:
        return trainer.dispatch_program_text()
    finally:
        trainer.close()


def scope_and_way(op_name: str) -> str:
    from benchmark.reduce import stream_scopes
    from benchmark.tools import program_trace

    scope = stream_scopes.scope_of(op_name)
    if scope is None:
        return program_trace.scope_of(op_name)[0]
    if "rematted_computation" in op_name:
        return scope + " again"
    return scope + (" bwd" if "transpose(" in op_name else " fwd" if "jvp(" in op_name else "")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="an .xplane.pb kept by run.py --keep-trace")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--top", type=int, default=30)
    args = ap.parse_args(argv)

    from benchmark.reduce import intervals as iv
    from benchmark.reduce import xplane
    from benchmark.tools import program_trace

    trace = program_trace.load(args.trace)
    if not trace.devices:
        print("stream_trace: no device plane in this trace", file=sys.stderr)
        return 1
    names = program_trace.instruction_scopes(program_text(args.workload))
    dev = trace.devices[0]
    busy = iv.total(dev.busy())
    by_scope: Dict[str, float] = {}
    by_op: Dict[Tuple[str, str], float] = {}
    for event, own in iv.self_times(dev.ops):
        instruction = event.partition(" = ")[0].strip().lstrip("%")
        op_name = names.get(instruction)
        scope = scope_and_way(op_name) if op_name else "(no op_name)"
        by_scope[scope] = by_scope.get(scope, 0.0) + own
        key = (xplane.op_name(event), scope)
        by_op[key] = by_op.get(key, 0.0) + own
    launches = xplane.launches(dev)
    print(f"{dev.name}: busy {busy:.4f} s, {len(launches)} launches of {xplane.train_program(dev)}")
    print("own device time by scope (fwd, made again in a backward, bwd):")
    for scope, own in sorted(by_scope.items(), key=lambda kv: -kv[1]):
        print(f"  {scope:<28} {own:9.4f} s  {100 * own / busy:6.2f}%")
    print(f"the {args.top} operations with most own time:")
    ranked = sorted(by_op.items(), key=lambda kv: -kv[1])
    for (op, scope), own in ranked[: args.top]:
        print(f"  {100 * own / busy:6.2f}%  {own:9.4f} s  {scope:<24} {op}")
    print("the operations outside every scope with most own time, and what the program calls them:")
    outside = [(op, own) for (op, scope), own in ranked if scope.startswith("(")][:12]
    for op, own in outside:
        name = names.get(op.split(" ")[0].lstrip("%"), "")
        print(f"  {100 * own / busy:6.2f}%  {own:9.4f} s  {op[:60]:<60} {name[-100:]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
