"""The trace reduction on a small recorded trace: ``hop-h1024.online-
steady`` on one v5e chip, ``--seconds 3 --trace 1`` (my chip run, PR 24,
seed 2002: five dispatches of eight steps).  The numbers it must give were
read from this file when it was recorded, and agree with what that run
printed."""

import gzip
import os
import shutil
from types import SimpleNamespace

import pytest

from benchmark import run
from benchmark.reduce import intervals as iv
from benchmark.reduce import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(os.path.join(HERE, "data", "online-steady-3s.xplane.pb.gz")) as src:
        with open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
    return xplane.load(str(path))


def test_planes_and_lines_are_found(trace):
    assert [d.name for d in trace.devices] == ["/device:TPU:0"]
    dev = trace.devices[0]
    assert len(dev.modules) == 5 and len(dev.ops) == 11735
    assert xplane.train_program(dev) == "jit__train_dispatch"
    assert {s[2] for s in trace.spans} == {
        "bench/window", "bench/run", "bench/drain", "bench/feed_blocked"
    }


def test_busy_and_gaps(trace):
    assert trace.busy_s() == pytest.approx(6.836794111, rel=1e-9)
    lo, hi = xplane.bounds(trace)
    assert (lo, hi) == pytest.approx((0.025505175, 6.862327346), abs=1e-9)
    gaps = iv.gaps(trace.devices[0].busy(), lo, hi)
    assert max(b - a for a, b in gaps) == pytest.approx(5.762e-6, rel=1e-3)
    # Host and device share a clock: the window's annotation holds every launch.
    window = next(s for s in trace.spans if s[2] == "bench/window")
    assert window[0] <= lo and hi <= window[1]


def test_metric_readers_on_the_recorded_run(trace):
    window = SimpleNamespace(
        steps=40, launches=5, records=40 * 524_288, elapsed_s=6.864415057999992,
        extras={"producer_blocked_s": 4.129, "steps_per_launch": 8},
    )
    r = SimpleNamespace(
        trace=trace, window=window, chips=1, compiles_in_window=0,
        memory_peak_bytes=7_401_437_696, peaks={"bf16_flops_per_s": 197e12},
        step_flops=18_852_864.0 * 524_288,
    )
    read = lambda name: run.load_module("metrics", name).read(r)
    assert read("step_device_ms") == pytest.approx(170.920284225, rel=1e-9)
    assert read("device_idle_share") == pytest.approx(0.40237874264030804, rel=1e-6)
    assert read("idle_gap_max_ms") == pytest.approx(0.005762, rel=1e-3)
    assert read("launch_gap_ms") == pytest.approx(0.0026372, rel=1e-3)
    # The shapes' FLOPs of a step over its traced device time, not the window.
    assert read("step_mfu") == pytest.approx(
        100 * 18_852_864 * 524_288 / (0.170920284225 * 197e12), rel=1e-9
    )
    assert read("step_mfu") == pytest.approx(29.3553606, rel=1e-8)
    assert read("peak_hbm_gb") == pytest.approx(7.401437696)
    assert read("job_overhead_share") is None           # no jobs in this cell
    assert read("collective_exposed_share") is None     # one chip, no collective


def test_breakdown_names_ops_by_instruction_and_shape(trace):
    ops = xplane.top_ops(trace, 3)
    assert ops[0][0].startswith("%convolution_add_fusion.13 bf16[524288,1024]")
    assert ops[0][1] == pytest.approx(0.7012696389999986, rel=1e-6)
    gaps = xplane.top_gaps(trace, *xplane.bounds(trace), 3)
    assert gaps[0][0] == "bench/feed_blocked" and gaps[0][1] == pytest.approx(5.762e-6, rel=1e-3)
