"""The readers of the program's own spans and counters (PR 25), each over
a tiny ``measure()`` on the CPU: a traced run gives every one of them a
value that agrees with what the window did, and with nothing in the ring
(tracing off, or a program from before it wrote these spans) each says
``None`` and raises nothing."""

import jax
import pytest

from benchmark import run
from benchmark.tests import tiny
from dragonfly2_tpu.utils import tracing

SEED = 2**31 + 321

RUN_READERS = (
    "run_starved_share", "block_assemble_ms", "h2d_ms", "enqueue_ms",
    "dispatches_in_flight_max", "records_count_gap",
)
JOB_READERS = ("job_init_s", "job_sync_ms")


def _read(name, r):
    return run.load_module("metrics", name).read(r)


@pytest.fixture(scope="module")
def traced():
    """One traced tiny window of the online cell and one of each job probe,
    on a ring of their own."""
    old, tracing.default_tracer.exporter = tracing.default_tracer.exporter, tracing.InMemoryExporter()
    try:
        # The counters are the process's: what other tests of this process
        # left between them (a planted fault's dropped rows) is taken off.
        out = {"gap_before": _read("records_count_gap", None) or 0.0}
        for workload in ("hop-h1024.online-steady", "hop-h1024.batch-job", "gat-c2.batch-job"):
            cell, config = tiny.cell(workload)
            got = run.measure(cell, config, SEED, 0.5, True, jax.devices()[: cell["chips"]])
            assert got["correct"], got["compared"]
            out[workload] = {
                "run": got["run"],
                "values": {n: _read(n, got["run"]) for n in RUN_READERS + JOB_READERS},
            }
        yield out
    finally:
        tracing.default_tracer.exporter = old


def test_the_online_cell_reads_all_six(traced):
    got = traced["hop-h1024.online-steady"]
    v, window = got["values"], got["run"].window
    assert all(v[n] is not None for n in RUN_READERS), v
    assert 0.0 <= v["run_starved_share"] <= 100.0
    assert v["block_assemble_ms"] >= 0.0 and v["h2d_ms"] > 0.0 and v["enqueue_ms"] > 0.0
    depth = got["run"].cell["driver_params"]["queue_capacity"] + 2
    assert 1 <= v["dispatches_in_flight_max"] <= min(depth, window.launches)
    assert v["records_count_gap"] == traced["gap_before"]
    assert v["job_init_s"] is None and v["job_sync_ms"] is None      # no job ran


@pytest.mark.parametrize("workload", ["hop-h1024.batch-job", "gat-c2.batch-job"])
def test_a_job_probe_reads_what_a_job_pays_before_and_between_steps(traced, workload):
    got = traced[workload]
    v, walls = got["values"], got["run"].window.extras["unit_walls_s"]
    assert 0.0 < v["job_init_s"] < sum(walls) / len(walls)
    assert v["job_sync_ms"] > 0.0
    # The jobs' spans are the window's: as many roots as jobs, each with
    # the steps the driver counts and the rows the steps counted.
    from benchmark.reduce import program_spans as ps

    jobs = ps.window_jobs(got["run"])
    per = got["run"].window.extras["launches_per_unit"]
    assert len(jobs) == len(walls)
    batch = got["run"].cell["driver_params"]["batch_size"]
    for spans in jobs:
        assert len(ps.named(spans, "train/step")) == per
        assert ps.named(spans, "train/job")[0].attributes["records_trained"] == per * batch
    assert all(v[n] is None for n in RUN_READERS if n != "records_count_gap")


def test_the_run_root_is_held_to_the_profilers_clock(traced):
    from benchmark.reduce import program_spans as ps

    r = traced["hop-h1024.online-steady"]["run"]
    root, spans = ps.window_run(r)
    assert root.attributes["dispatches"] == r.window.launches
    assert len(ps.named(spans, "trainer/dispatch")) == r.window.launches
    # An annotation that disagrees by more than 5 ms reads as no root.
    lo, hi, name = next(s for s in r.trace.spans if s[2] == "bench/run")
    import copy

    skewed = copy.copy(r)
    skewed.trace = copy.copy(r.trace)
    skewed.trace.spans = [s for s in r.trace.spans if s[2] != "bench/run"] + [(lo, hi + 0.006, name)]
    assert ps.window_run(skewed) is None and _read("h2d_ms", skewed) is None


def test_nothing_in_the_ring_reads_as_none(traced, monkeypatch):
    monkeypatch.setattr(tracing.default_tracer, "exporter", tracing.InMemoryExporter())
    from dragonfly2_tpu.utils.metrics import default_registry

    monkeypatch.setattr(default_registry, "get", lambda name: None)    # the parent has no such counter
    for workload in ("hop-h1024.online-steady", "hop-h1024.batch-job", "gat-c2.batch-job"):
        got = traced[workload]
        for name in RUN_READERS + JOB_READERS:
            assert _read(name, got["run"]) is None, (workload, name)


def test_every_reader_is_listed_or_states_its_unit():
    spec = run.load_json(run.ROOT, "BENCHMARK.json")
    listed = {m["name"]: m for m in spec["per_layer"]}
    for name in RUN_READERS:
        assert listed[name]["moves"] == "records_per_s_per_chip"
        assert listed[name]["workloads"] == ["hop-h1024.online-steady"]
    for name in JOB_READERS:
        assert name not in listed and run.load_module("metrics", name).UNIT
