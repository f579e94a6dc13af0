"""One run of one cell:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Holds the cell's chips in this one process, refuses to run without a TPU,
makes every input from ``--seed``, warms the cell's own shapes (set-up),
measures for ``--seconds``, then decides ``correct`` against the plain
reference and prints one JSON line.  ``--trace 0`` reports the end-to-end
metrics, ``--trace 1`` the per-layer metrics read from a profiler trace of
the same window.  ``--probe 1`` runs by hand a cell of ``probes/``, which
``BENCHMARK.json`` does not list, and asks it for every metric there is.

Everything about a cell, a configuration, a driver, a reference or a
per-layer metric is found by its name in a file of its own; this module
holds no list of any of them.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
from dataclasses import dataclass
from typing import Any, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module, whatever characters the
    name has."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"nothing named {name!r} under benchmark/{kind}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('-', '_').replace('.', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def device_peaks(device_kind: str) -> dict:
    peaks = load_json(HERE, "peaks.json")
    if device_kind not in peaks or device_kind.startswith("_"):
        raise ValueError(
            f"no peaks on record for device kind {device_kind!r}: add it to "
            f"benchmark/peaks.json with its source"
        )
    return peaks[device_kind]


class CompileCounter:
    """Programs XLA compiled (persistent-cache misses; a program read back
    from the cache is a load, not a compile), counted only while ``open``.
    The benchmark's copy of ``chip_smoke.py``'s listener."""

    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.open = False
        self.count = 0
        mon.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kw) -> None:
        if self.open and event == self._MISS:
            self.count += 1


@dataclass
class Ctx:
    """What a driver's ``setup`` gets."""

    cell: dict
    config: dict
    config_module: Any
    inputs: Any
    seed: int
    devices: list


@dataclass
class Run:
    """What a per-layer metric's reader gets."""

    cell: dict
    config: dict
    peaks: dict
    chips: int
    window: Any                      # drivers.online.Window
    step_flops: float
    compiles_in_window: int
    memory_peak_bytes: int
    trace: Optional[Any] = None      # reduce.xplane.Trace, in a traced run


class MemoryPeak:
    """The most device memory held at once during the window, fullest
    device: ``bytes_in_use`` + ``bytes_reserved``, sampled every 20 ms.

    The TPU runtime keeps what loaded programs need for their temporaries
    (reserved) apart from the buffers JAX hands out (in use): a program
    with 2 GiB of temporaries leaves ``peak_bytes_in_use`` at 0.2 GB and
    ``peak_bytes_reserved`` at 2.2 GB.  Neither peak alone is the chip's,
    and their sum overstates it where they fall at different times (it read
    22.4 GB of 16.9 in a job cell), so the sum is taken as it stands at each
    instant (my chip runs, PR 24)."""

    def __init__(self, devices, period_s: float = 0.02) -> None:
        import threading

        self.devices, self.period_s = devices, period_s
        self.peak = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, name="bench-memory", daemon=True)

    def sample(self) -> None:
        for d in self.devices:
            stats = d.memory_stats() or {}
            now = int(stats.get("bytes_in_use", 0)) + int(stats.get("bytes_reserved", 0))
            self.peak = max(self.peak, now)

    def _loop(self) -> None:
        while not self._stop.wait(self.period_s):
            self.sample()

    def __enter__(self) -> "MemoryPeak":
        self.sample()
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join(timeout=10.0)
        self.sample()


def load_cell_files(workload: str):
    """A cell's own file and its configuration's.  A cell of
    ``BENCHMARK.json`` lies under ``workloads/``; one that waits for a later
    PR, or that only sizes another, under ``probes/``."""
    kind = next(
        (k for k in ("workloads", "probes") if os.path.exists(os.path.join(HERE, k, f"{workload}.json"))),
        "workloads",
    )
    cell = load_json(HERE, kind, f"{workload}.json")
    config = load_json(HERE, "configs", f"{cell['config']}.json")
    cell["traffic"].setdefault("num_nodes", config["graph"]["num_nodes"])
    cell["traffic"].setdefault("probe_degree", config["graph"]["probe_degree"])
    return cell, config


def load_cell(workload: str, probe: bool):
    spec = load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if entry is None and not probe:
        raise SystemExit(
            f"run: BENCHMARK.json has no workload {workload!r}; --probe 1 runs a cell of "
            f"benchmark/probes by hand"
        )
    if entry is not None and probe:
        raise SystemExit(f"run: {workload!r} is a workload of BENCHMARK.json, not a probe")
    cell, config = load_cell_files(workload)
    for key in ("config", "chips"):
        if entry is not None and cell[key] != entry[key]:
            raise SystemExit(f"run: {workload}: {key} differs between BENCHMARK.json and the cell's file")
    return spec, cell, config


def metric_names(spec: dict, group: str, workload: str, probe: bool = False) -> list:
    """The metrics this cell reports.  A probe is listed by none: it is
    asked for every end-to-end metric and every reader there is (one that
    waits with its cell states its ``UNIT`` itself), and a reader with
    nothing to read says so."""
    if not probe:
        return [m for m in spec[group] if "workloads" not in m or workload in m["workloads"]]
    if group != "per_layer":
        return list(spec[group])
    units = {m["name"]: m["unit"] for m in spec[group]}
    names = sorted(f[:-3] for f in os.listdir(os.path.join(HERE, "metrics")) if f.endswith(".py"))
    return [
        {"name": n, "unit": units.get(n) or load_module("metrics", n).UNIT} for n in names
    ]


def measure(
    cell: dict, config: dict, seed: int, seconds: float, trace: bool, devices,
    keep_trace: Optional[str] = None,
) -> dict:
    """Set-up, window and comparison of one cell on ``devices``; what
    ``main`` prints.  Has no opinion on what the devices are, so the tests
    drive it at a tiny size on the CPU."""
    import jax

    from benchmark import check, traffic
    from benchmark.reduce import xplane

    config_module = load_module("configs", cell["config"])
    driver = load_module("drivers", cell["driver"])
    reference = load_module("reference", cell["config"])
    counter = CompileCounter()

    inputs = traffic.make_inputs(cell["traffic"], seed)
    session = driver.setup(Ctx(cell, config, config_module, inputs, seed, devices))
    setup_s = time.perf_counter() - _PROCESS_START

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    counter.open = True
    try:
        with MemoryPeak(devices) as memory, jax.profiler.TraceAnnotation("bench/window"):
            window = session.run_window(seconds)
    finally:
        counter.open = False
        if trace:
            jax.profiler.stop_trace()
    peak = memory.peak

    # The program's state goes before the reference comes: the peak is
    # read, and the reference needs the room.
    reference_inputs = session.reference_inputs
    session.release()
    gc.collect()
    with jax.default_matmul_precision("highest"):
        ref = reference.first_steps(config["model"], config["train"], reference_inputs)
    first = session.first_readings(ref["init_params"])
    rows = check.compare(first, ref, cell["limits"], extra=window.extras)
    if os.environ.get("BENCH_BY_LEAF"):
        print("by_leaf " + json.dumps(check.by_leaf(first, ref)), file=sys.stderr)

    run = Run(
        cell=cell, config=config, peaks=None, chips=len(devices), window=window,
        step_flops=config_module.step_flops(
            config["model"], config["graph"], int(cell["driver_params"]["batch_size"])
        ),
        compiles_in_window=counter.count, memory_peak_bytes=peak,
    )
    out = {
        "correct": check.verdict(rows), "attempted": window.launches, "failed": 0,
        "setup_s": setup_s, "run": run, "compared": rows,
    }
    if trace:
        path = xplane.find(TRACE_DIR)
        run.trace = xplane.load(path)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(path, keep_trace)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, help="copy the .xplane.pb into this directory")
    ap.add_argument("--probe", type=int, choices=(0, 1), default=0,
                    help="run a cell of benchmark/probes, which BENCHMARK.json does not list")
    args = ap.parse_args(argv)

    spec, cell, config = load_cell(args.workload, bool(args.probe))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(
            f"run: {args.workload} needs {cell['chips']} TPU chip(s), found "
            f"{len(devices)} x {devices[0].platform} ({devices[0].device_kind}); "
            f"nothing measured", file=sys.stderr,
        )
        return 2
    devices = devices[: cell["chips"]]
    peaks = device_peaks(devices[0].device_kind)   # an unknown kind fails before any work

    from dragonfly2_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    got = measure(cell, config, args.seed, args.seconds, bool(args.trace), devices, args.keep_trace)
    run: Run = got["run"]
    run.peaks = peaks
    window = run.window

    metrics = {}
    if args.trace:
        for m in metric_names(spec, "per_layer", args.workload, bool(args.probe)):
            value = load_module("metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        values = {
            "records_per_s_per_chip": window.records / window.elapsed_s / run.chips,
            "setup_s": got["setup_s"],
        }
        for m in metric_names(spec, "end_to_end", args.workload, bool(args.probe)):
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}

    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices()), "memory_peak_bytes": run.memory_peak_bytes,
    }
    line = {
        "correct": got["correct"], "attempted": got["attempted"], "failed": got["failed"],
        "metrics": metrics, "device": device,
    }
    if args.trace:
        from benchmark.reduce import xplane

        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = window.elapsed_s
        line["breakdown"] = {
            "device_ops": xplane.top_ops(run.trace),
            "idle_gaps": xplane.top_gaps(run.trace, *xplane.bounds(run.trace)),
        }
    line["window"] = {
        "records": window.records, "steps": window.steps, "elapsed_s": window.elapsed_s,
        "setup_s": got["setup_s"], "compiles_in_window": run.compiles_in_window,
        "unit_walls_s": window.extras.get("unit_walls_s"),
        "total_s": time.perf_counter() - _PROCESS_START,
    }
    line["compared"] = got["compared"]
    for row in got["compared"]:
        print(f"compared {row['name']}: {row['value']:.6g} (limit {row['limit']:.6g})", file=sys.stderr)
    print(f"correct: {got['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
