"""The program's own spans, read where it keeps them: the in-memory ring
of ``dragonfly2_tpu.utils.tracing.default_tracer`` in the benchmark's
process.  A reader that finds no such span (tracing off, or a program
from before it wrote any) has nothing to read and says so with ``None``.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

# The ring stamps ``time.time_ns()``, the profiler its own clock rebased to
# the session: the same span may not differ in length by more than this.
CLOCKS_AGREE_S = 0.005


def ring():
    from dragonfly2_tpu.utils.tracing import InMemoryExporter, default_tracer

    exporter = default_tracer.exporter
    return exporter if isinstance(exporter, InMemoryExporter) else None


def seconds(span) -> float:
    return (span.end_ns - span.start_ns) / 1e9


def window_run(run) -> Optional[Tuple[object, List]]:
    """(root, the spans of its trace) of the window's ``trainer/run``: the
    last one in the ring, held to the ``bench/run`` annotation that the
    driver put around the same call in the profiler's trace."""
    spans = ring()
    roots = spans.find("trainer/run") if spans is not None else []
    if not roots or run.trace is None:
        return None
    root = roots[-1]
    around = [s for s in run.trace.spans if s[2] == "bench/run"]
    if not around or abs((around[-1][1] - around[-1][0]) - seconds(root)) > CLOCKS_AGREE_S:
        return None
    return root, spans.trace(root.trace_id)


def named(spans: List, name: str) -> List:
    return [s for s in spans if s.name == name]


def mean_ms(spans: List) -> Optional[float]:
    return 1e3 * sum(seconds(s) for s in spans) / len(spans) if spans else None


def window_jobs(run) -> Optional[List[List]]:
    """The spans of each job of the window, one list a job: the last
    ``len(unit_walls_s)`` ``train/job`` roots in the ring."""
    walls = run.window.extras.get("unit_walls_s")
    spans = ring()
    roots = spans.find("train/job") if spans is not None else []
    if not walls or len(roots) < len(walls):
        return None
    return [spans.trace(root.trace_id) for root in roots[-len(walls):]]
