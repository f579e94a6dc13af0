"""Read a kept trace by the program's own names.

    python3 benchmark/run.py --workload <cell> ... --trace 1 --keep-trace <dir>
    python3 benchmark/tools/program_trace.py <dir>/<file>.xplane.pb [--workload <cell>]

Three tables.  The program's spans (``trainer/...``, ``train/...``: the
``TraceAnnotation`` every ``default_tracer.span`` is, PR 25) by name, with
their own time: a span's length less what the spans inside it cover, so
``trainer/run``'s is the loop's bookkeeping.  Every idle gap of the first
device over 1 ms, with the innermost span of the program open at its
middle, and their sum by name.  And, where ``--workload`` names a cell of
the online driver, the device's own time per scope of the step: each
operation's instruction name is looked up in the compiled train dispatch
(``OnlineGraphTrainer.dispatch_program_text()``, built here at the cell's
sizes: the same program, from the persistent cache where the run left it)
and the ``op_name`` found there is cut down to the ``jax.named_scope`` the
model gave it (``hop/src``, ``optimizer``, ...), forward or backward.

A hand tool: ``run.py``'s own breakdown names gaps by ``bench/`` spans
alone until a ``benchmark`` PR widens ``reduce/xplane.py``'s prefix, and
keeps no program text, so no metric reads this yet.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PROGRAM_PREFIXES = ("trainer/", "train/")
GAP_S = 1e-3

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# Components of an ``op_name`` that say how jax staged the step, not where
# in the model the operation is.
_STAGING = re.compile(r"^(jit\(.*\)|while|body|cond|closed_call|checkpoint|_graph_train_step)$")


def load(path: str):
    """``reduce.xplane.load`` with the program's spans kept beside the
    benchmark's (its prefix is read at each call and may be a tuple)."""
    from benchmark.reduce import xplane

    old = xplane.ANNOTATION_PREFIX
    xplane.ANNOTATION_PREFIX = (old, *PROGRAM_PREFIXES)
    try:
        return xplane.load(path)
    finally:
        xplane.ANNOTATION_PREFIX = old


def named_gaps(trace, least_s: float = GAP_S) -> List[Tuple[float, float, str]]:
    """(start, seconds, name) of every idle gap of the first device of at
    least ``least_s``, named by the innermost program span open at its
    middle, else the innermost of the benchmark's, else ``unattributed``."""
    from benchmark.reduce import intervals as iv
    from benchmark.reduce import xplane

    dev = trace.devices[0]
    out = []
    for a, b in iv.gaps(dev.busy(), *xplane.bounds(trace)):
        if b - a < least_s:
            continue
        mid = (a + b) / 2
        open_ = [s for s in trace.spans if s[0] <= mid <= s[1]]
        ours = [s for s in open_ if s[2].startswith(PROGRAM_PREFIXES)]
        pick = ours or open_
        name = min(pick, key=lambda s: s[1] - s[0])[2] if pick else "unattributed"
        out.append((a, b - a, name))
    return out


def span_times(trace) -> List[Tuple[str, int, float, float]]:
    """(name, count, seconds, own seconds) of the program's spans.  They
    nest on the thread that opened them; the benchmark's are left out."""
    from benchmark.reduce import intervals as iv

    ours = [s for s in trace.spans if s[2].startswith(PROGRAM_PREFIXES)]
    out: Dict[str, List[float]] = {}
    for lo, hi, name in ours:
        row = out.setdefault(name, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += hi - lo
    for name, own in iv.self_times(ours):
        out[name][2] += own
    return [(name, *row) for name, row in sorted(out.items(), key=lambda kv: -kv[1][1])]


def instruction_scopes(program_text: str) -> Dict[str, str]:
    """instruction name -> ``op_name``, for every line of a compiled
    program's text that carries one."""
    out: Dict[str, str] = {}
    for line in program_text.splitlines():
        head, meta = _INSTRUCTION.match(line), _OP_NAME.search(line)
        if head and meta:
            out.setdefault(head.group(1), meta.group(1))
    return out


def scope_of(op_name: str) -> Tuple[str, str]:
    """(scope, rest) of an ``op_name``: the ``named_scope`` the model or the
    step gave the operation, marked ``fwd`` or ``bwd`` where autodiff
    says, and the modules and primitive below it."""
    parts = [p for p in op_name.split("/") if not _STAGING.match(p)]
    way = ""
    if any(p.startswith("transpose(") for p in parts):
        way = " bwd"
    elif any(p.startswith("jvp(") for p in parts):
        way = " fwd"
    for i, p in enumerate(parts):
        if p in ("hop", "gat") and i + 1 < len(parts):
            return f"{p}/{parts[i + 1]}{way}", "/".join(parts[i + 2:])
        if p in ("loss", "jvp(loss)", "transpose(jvp(loss))"):
            return f"loss{way}", "/".join(parts[i + 1:])
        if p == "optimizer":
            return "optimizer", "/".join(parts[i + 1:])
    return "(outside every scope)", "/".join(parts)


def scope_times(trace, scopes: Dict[str, str]):
    """Own device time (first device) summed by scope, and by operation
    with its scope: ([scope, seconds], [instruction and shape, scope, rest,
    seconds]), largest first."""
    from benchmark.reduce import intervals as iv
    from benchmark.reduce import xplane

    dev = trace.devices[0]
    by_scope: Dict[str, float] = {}
    by_op: Dict[Tuple[str, str, str], float] = {}
    for event, own in iv.self_times(dev.ops):
        instruction = event.partition(" = ")[0].strip().lstrip("%")
        op_name = scopes.get(instruction)
        scope, rest = scope_of(op_name) if op_name else ("(no op_name)", "")
        by_scope[scope] = by_scope.get(scope, 0.0) + own
        key = (xplane.op_name(event), scope, rest)
        by_op[key] = by_op.get(key, 0.0) + own
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])
    return rank(by_scope), [[*k, v] for k, v in rank(by_op)]


def online_program_text(workload: str) -> Optional[str]:
    """The train dispatch of an online cell, compiled at the cell's sizes;
    None for a cell of another driver (a job makes its step program inside
    the entry point and hands back no handle to it)."""
    import numpy as np

    from benchmark import run
    from dragonfly2_tpu.trainer.online_graph import OnlineGraphConfig, OnlineGraphTrainer
    from dragonfly2_tpu.utils.compile_cache import enable_compile_cache

    cell, config = run.load_cell_files(workload)
    if cell["driver"] != "online":
        return None
    enable_compile_cache()
    p, graph = cell["driver_params"], config["graph"]
    mesh = None
    if p.get("mesh"):
        from dragonfly2_tpu.parallel.mesh import MeshSpec, create_mesh

        mesh = create_mesh(MeshSpec(data=int(p["mesh"]["data"])))
    # The program depends on shapes alone: no seed, an empty graph.
    nothing = np.zeros(0, np.int32)
    trainer = OnlineGraphTrainer(
        OnlineGraphConfig(
            num_nodes=graph["num_nodes"], max_neighbors=graph["max_neighbors"],
            batch_size=int(p["batch_size"]), super_steps=int(p["super_steps"]),
            queue_capacity=int(p["queue_capacity"]),
            model=run.load_module("configs", cell["config"]).model_config(config["model"]),
            mesh=mesh,
        ),
        node_feats=np.zeros((graph["num_nodes"], graph["node_feature_dim"]), np.float32),
        topo_src=nothing, topo_dst=nothing, topo_rtt=nothing.astype(np.float32),
    )
    try:
        return trainer.dispatch_program_text()
    finally:
        trainer.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="an .xplane.pb kept by run.py --keep-trace")
    ap.add_argument("--workload", default=None,
                    help="the cell the trace is of: an online cell adds the per-scope table")
    ap.add_argument("--top", type=int, default=24, help="operations to list")
    args = ap.parse_args(argv)

    trace = load(args.trace)
    if not trace.devices:
        print("program_trace: no device plane in this trace", file=sys.stderr)
        return 1
    busy = trace.busy_s()

    print("the program's spans: count, seconds, own seconds, mean ms")
    for name, n, total, own in span_times(trace):
        print(f"  {name:<24} n={n:<6} {total:10.4f} s  own {own:10.6f} s  mean {1e3 * total / n:10.4f} ms")

    gaps = named_gaps(trace)
    print(f"idle gaps of at least {GAP_S * 1e3:g} ms on {trace.devices[0].name}: {len(gaps)}")
    by_name: Dict[str, List[float]] = {}
    for _, length, name in gaps:
        by_name.setdefault(name, []).append(length)
    for name, lengths in sorted(by_name.items(), key=lambda kv: -sum(kv[1])):
        print(f"  {name:<24} n={len(lengths):<5} sum={sum(lengths):9.4f} s  "
              f"mean={1e3 * sum(lengths) / len(lengths):9.3f} ms  max={1e3 * max(lengths):9.3f} ms")
    for start, length, name in gaps[:200]:
        print(f"    at {start:9.4f} s  {1e3 * length:10.3f} ms  {name}")

    text = online_program_text(args.workload) if args.workload else None
    if text is None:
        print("no program text: per-scope table left out")
        return 0
    by_scope, by_op = scope_times(trace, instruction_scopes(text))
    print(f"own device time by scope ({trace.devices[0].name}, busy {busy:.4f} s):")
    for scope, own in by_scope:
        print(f"  {scope:<24} {own:9.4f} s  {100 * own / busy:6.2f}%")
    print(f"the {args.top} operations with most own time:")
    for op, scope, rest, own in by_op[: args.top]:
        print(f"  {100 * own / busy:6.2f}%  {own:9.4f} s  {scope:<18} {rest:<44} {op}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
