"""Reading a JAX profiler trace (``.xplane.pb``) with nothing but JAX.

A device plane (``/device:TPU:<n>``) has a line of whole programs ("XLA
Modules") and a line of the operations inside them ("XLA Ops"); the host
plane has one line per thread, on which ``jax.profiler.TraceAnnotation``
spans appear under their own names.  All times come back in seconds from
the trace's earliest event.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

from . import intervals as iv

Event = Tuple[float, float, str]        # (start_s, end_s, name)

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
ANNOTATION_PREFIX = "bench/"
COLLECTIVE_MARKS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "allreduce", "allgather",
)


@dataclass
class Device:
    name: str
    modules: List[Event] = field(default_factory=list)
    ops: List[Event] = field(default_factory=list)

    def busy(self) -> List[iv.Interval]:
        """Union of the intervals in which an operation ran."""
        src = self.ops or self.modules
        return iv.union((a, b) for a, b, _ in src)


@dataclass
class Trace:
    devices: List[Device]
    spans: List[Event]                   # the benchmark's own annotations

    def busy_s(self) -> float:
        """Seconds an operation ran, averaged over the devices."""
        return sum(iv.total(d.busy()) for d in self.devices) / len(self.devices)


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    raw: Dict[str, Dict[str, List[Event]]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:") and "TPU" in plane.name
        for line in plane.lines:
            if is_device and line.name in (MODULES_LINE, OPS_LINE):
                dest = raw.setdefault(plane.name, {}).setdefault(line.name, [])
                for ev in line.events:
                    dest.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
            elif plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    starts = [e[0] for lines in raw.values() for evs in lines.values() for e in evs]
    starts += [s[0] for s in spans]
    zero = min(starts) if starts else 0.0
    sec = lambda evs: sorted(((a - zero) / 1e9, (b - zero) / 1e9, n) for a, b, n in evs)
    devices = [
        Device(name, sec(lines.get(MODULES_LINE, [])), sec(lines.get(OPS_LINE, [])))
        for name, lines in sorted(raw.items())
    ]
    return Trace(devices=[d for d in devices if d.modules or d.ops], spans=sec(spans))


def bounds(trace: Trace) -> Tuple[float, float]:
    """First start and last end of anything on a device."""
    evs = [e for d in trace.devices for e in (d.ops or d.modules)]
    return min(e[0] for e in evs), max(e[1] for e in evs)


def program_name(module_event_name: str) -> str:
    """``jit__train_dispatch(1234567)`` -> ``jit__train_dispatch``."""
    return module_event_name.split("(")[0]


def train_program(device: Device) -> str:
    """The program that took most device time: the train step (or the
    scan of steps) in every cell this benchmark has."""
    by: Dict[str, float] = {}
    for a, b, n in device.modules:
        by[program_name(n)] = by.get(program_name(n), 0.0) + (b - a)
    if not by:
        raise ValueError(f"no programs on {device.name}")
    return max(by, key=by.get)


def launches(device: Device) -> List[iv.Interval]:
    name = train_program(device)
    return [(a, b) for a, b, n in device.modules if program_name(n) == name]


def op_name(event_name: str) -> str:
    """An operation's event carries its whole HLO line; keep the
    instruction's name and the shape it makes."""
    head, _, rest = event_name.partition(" = ")
    return f"{head} {rest.split(' ')[0]}"[:120] if rest else head[:120]


def is_collective(op_name: str) -> bool:
    low = op_name.lower()
    return any(m in low for m in COLLECTIVE_MARKS)


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The operations that took most (own) device time, first device."""
    dev = trace.devices[0]
    by: Dict[str, float] = {}
    for name, own in iv.self_times(dev.ops or dev.modules):
        name = op_name(name)
        by[name] = by.get(name, 0.0) + own
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(trace: Trace, lo: float, hi: float, n: int = 10) -> List[List]:
    """The longest idle gaps of the first device, each named by the
    innermost benchmark annotation open at its middle."""
    dev = trace.devices[0]
    out = []
    for a, b in sorted(iv.gaps(dev.busy(), lo, hi), key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        open_ = [s for s in trace.spans if s[0] <= mid <= s[1]]
        name = min(open_, key=lambda s: s[1] - s[0])[2] if open_ else "unattributed"
        out.append([name, b - a])
    return out
