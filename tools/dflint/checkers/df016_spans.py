"""DF016 — span coverage.

The flight recorder (utils/tracing.py DurableSpanExporter + the span
sites across every plane, DESIGN.md §21) is only as good as the spans
that feed it: delete one ``remote_span`` from an RPC server and every
cross-process trace silently loses that hop — nothing else fails.  This
rule is the static half of the coverage contract (the runtime half is
``utils/dfspan.py`` + ``tests/test_zz_spanwitness.py``, in the
lock/compile/crash-witness mould).

Two sub-rules:

1. **Inventory** — ``REQUIRED_SPANS`` pins each instrumented module to
   the span names it must open (``tracer.span("name")`` /
   ``tracer.remote_span(f"rpc/{m}")``; f-string sites match on their
   constant prefix as ``prefix*``).  Deleting ANY inventoried span site
   fails tier-1 by file name.  New spans: add the site here when you add
   the instrumentation.

2. **Server-entry adjacency** — every RPC server entry (a call to the
   shared ``adapter.dispatch(...)``) must have a ``remote_span`` opened
   in the same function, so the handler span exists on EVERY transport
   binding and carries the caller's traceparent.  An adapter dispatched
   outside a remote_span is an un-traced plane entry.

Inventory staleness (an entry naming a module that no longer exists) is
checked by ``stale_inventory_entries`` and wired into tier-1 like the
§16 lock graph (tests/test_dflint.py).
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Iterator, List, Set, Tuple

from ..core import Finding, Module, dotted, walk_calls

RULE = "DF016"
TITLE = "span coverage lost (missing inventoried span / untraced server entry)"

# relpath -> span names that module must open.  F-string sites are
# matched on their constant prefix (``rpc/*``).  The flight recorder's
# coverage contract, checked in.
REQUIRED_SPANS = {
    "dragonfly2_tpu/rpc/scheduler_server.py": ("rpc/*",),
    "dragonfly2_tpu/rpc/grpc_transport.py": ("rpc/*",),
    "dragonfly2_tpu/daemon/conductor.py": (
        "daemon/download", "daemon/piece", "daemon/source.piece",
        # Pass-through serve (DESIGN.md §25): rides the download span's
        # traceparent so a proxy/gateway serve lands on the SAME trace
        # as the swarm transfer that fed it.
        "daemon/stream", "daemon/*",
    ),
    "dragonfly2_tpu/daemon/piece_pipeline.py": ("daemon/report.flush",),
    "dragonfly2_tpu/manager/rest.py": ("manager/GET", "manager/POST"),
    "dragonfly2_tpu/jobs/preheat.py": (
        "jobs/preheat", "jobs/preheat.execute",
    ),
    "dragonfly2_tpu/rollout/controller.py": ("rollout/transition",),
    # The online trainer's phases, on the profiler's clock too (DESIGN.md
    # §21): the benchmark's readers (benchmark/metrics/) and
    # benchmark/tools/program_trace.py find them by these names.
    # ``trainer/run`` also carries what the ranker's ``run_attrs`` hook
    # says of its step (the stream ranker: ``moe_row_mover``,
    # ``gdn_scan_carrier``).
    "dragonfly2_tpu/trainer/online_graph.py": (
        "trainer/run", "trainer/next_block", "trainer/dispatch",
        "trainer/recycle", "trainer/h2d", "trainer/enqueue",
        "trainer/refresh", "trainer/checkpoint",
    ),
    # The batch job's phases (_train_graph_model).
    "dragonfly2_tpu/trainer/train.py": (
        "train/job", "train/init", "train/shuffle", "train/batch",
        "train/step", "train/step_sync", "train/validate",
    ),
    "dragonfly2_tpu/manager/replication.py": ("manager/replicate.commit",),
    "dragonfly2_tpu/scheduler/microbatch.py": ("scheduler/eval.flush",),
    # Cross-shard task migration (DESIGN.md §24): the handoff sweep is
    # the edge trace_assemble must show on the chaos drill's critical
    # path — losing the span loses the migration evidence.
    "dragonfly2_tpu/scheduler/sharding.py": ("scheduler/shard.handoff",),
    # SLO-autopilot adjustments (DESIGN.md §26): every shed-floor/cap
    # change closes one span — the flight recorder's answer to "why did
    # the autopilot shed at 12:03"; losing it loses the feedback-loop
    # evidence.
    "dragonfly2_tpu/qos/autopilot.py": ("scheduler/qos.autopilot",),
    # Lifecycle plane (DESIGN.md §29): every unattended train→export→
    # register epoch and every arbitration/promotion sweep closes one
    # span — the evidence trail for "who promoted this model at 12:03".
    "dragonfly2_tpu/lifecycle/daemon.py": (
        "lifecycle/epoch", "lifecycle/promote",
    ),
}


def _is_span_call(call: ast.Call) -> bool:
    """``<tracer>.span(...)`` / ``<tracer>.remote_span(...)`` — the
    receiver must look like a tracer so dict ``.span`` lookalikes don't
    count as coverage."""
    if not isinstance(call.func, ast.Attribute):
        return False
    if call.func.attr not in ("span", "remote_span"):
        return False
    recv = dotted(call.func.value) or ""
    leaf = recv.split(".")[-1]
    return "tracer" in leaf


def span_sites(module: Module) -> Set[str]:
    """Span names opened in this module; f-string sites normalize to
    their constant prefix + ``*`` (``remote_span(f"rpc/{m}")`` →
    ``rpc/*``).  Shared with the runtime span witness
    (tests/test_zz_spanwitness.py) as the static site index."""
    sites: Set[str] = set()
    for call in walk_calls(module.tree):
        if not _is_span_call(call) or not call.args:
            continue
        arg = call.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            sites.add(arg.value)
        elif isinstance(arg, ast.JoinedStr):
            prefix = ""
            for part in arg.values:
                if isinstance(part, ast.Constant):
                    prefix += str(part.value)
                else:
                    break
            sites.add(prefix + "*")
    return sites


def site_matches(site: str, name: str) -> bool:
    """Does a runtime span ``name`` satisfy inventory ``site``?"""
    if site.endswith("*"):
        return name.startswith(site[:-1])
    return name == site


def stale_inventory_entries(root: Path) -> List[str]:
    """Inventory entries whose module no longer exists — the staleness
    check tier-1 runs so the contract can't rot silently."""
    return [rel for rel in REQUIRED_SPANS if not (root / rel).is_file()]


def _is_adapter_dispatch(call: ast.Call) -> bool:
    name = dotted(call.func)
    if not name or not name.endswith(".dispatch"):
        return False
    recv = name[: -len(".dispatch")]
    return recv.split(".")[-1] == "adapter"


def _scope_has_remote_span(module: Module, node: ast.AST) -> bool:
    scope = module.enclosing_function(node) or module.tree
    for call in walk_calls(scope):
        if (
            isinstance(call.func, ast.Attribute)
            and call.func.attr == "remote_span"
        ):
            return True
    return False


def check(module: Module) -> Iterator[Finding]:
    # Sub-rule 1: inventory.
    required: Tuple[str, ...] = REQUIRED_SPANS.get(module.relpath, ())
    if required:
        present = span_sites(module)
        for site in required:
            if site not in present:
                yield module.finding(
                    RULE,
                    module.tree,
                    f"required span site {site!r} is missing — the flight "
                    "recorder lost coverage of this plane (REQUIRED_SPANS "
                    "in tools/dflint/checkers/df016_spans.py)",
                )

    # Sub-rule 2: server-entry adjacency.
    for call in walk_calls(module.tree):
        if not _is_adapter_dispatch(call):
            continue
        if _scope_has_remote_span(module, call):
            continue
        yield module.finding(
            RULE,
            call,
            "RPC server entry dispatches without a remote_span in the "
            "same function — this transport's handler spans (and the "
            "caller's traceparent) are lost to the flight recorder",
        )
