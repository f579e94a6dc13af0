"""Each cell driven as a function at a tiny size on the CPU, through the
same ``measure`` a chip run goes through (the look for a chip is
``main``'s, and is skipped): a sound run comes out correct, and a run with
the timed path broken underneath, or with the control in the program's
place, does not.

The limits are the cells' own.  The cells that wait under ``probes/`` are
driven too, so that their drivers stay whole; the mesh cell runs on four
virtual CPU devices.
"""

import jax
import pytest

from benchmark import check, run
from benchmark.tests import tiny
from benchmark.tools import controls

SEED = 2**31 + 123      # the driver's seeds are larger than 32 signed bits hold


def _devices(cell):
    return jax.devices()[: cell["chips"]]


def _measure(workload, trace=False, seconds=0.5):
    cell, config = tiny.cell(workload)
    return run.measure(cell, config, SEED, seconds, trace, _devices(cell))


@pytest.mark.parametrize("workload", tiny.workloads())
def test_sound_run_is_correct_and_counts_whole_units(workload):
    got = _measure(workload)
    assert got["correct"], got["compared"]
    window = got["run"].window
    per = window.extras["steps_per_launch"]
    assert window.steps == window.launches * per > 0
    assert window.records == window.steps * got["run"].cell["driver_params"]["batch_size"]
    assert window.elapsed_s >= 0.5


def _unchanged(real):
    def step(state, *args):
        _, loss = real(state, *args)
        return state, loss
    return step


def _part_of_batch(share):
    def make(real):
        def step(state, nf, table, src, dst, target, qef):
            n = src.shape[0] // share
            return real(state, nf, table, src[:n], dst[:n], target[:n], qef)
        return step
    return make


FAULTS = {
    "state_unchanged": _unchanged,
    "half_batch_left_out": _part_of_batch(2),
    # What a missing gradient exchange leaves on a device: the mean over
    # its own quarter of the batch.
    "no_exchange": _part_of_batch(4),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", tiny.workloads())
def test_broken_timed_path_is_not_correct(workload, fault, monkeypatch):
    if fault == "no_exchange" and tiny.cell(workload)[0]["chips"] == 1:
        pytest.skip("one chip exchanges nothing")
    if not tiny.sees(workload, fault):
        pytest.skip("a cell that waits under probes/, for a number that sees this fault among others")
    from dragonfly2_tpu.trainer import online_graph, train

    broken = FAULTS[fault](train._graph_train_step)
    monkeypatch.setattr(train, "_graph_train_step", broken)
    monkeypatch.setattr(online_graph, "_graph_train_step", broken)
    got = _measure(workload)
    assert not got["correct"], got["compared"]


@pytest.mark.parametrize("workload", tiny.workloads())
def test_control_in_a_lower_precision_is_not_correct(workload):
    cell, config = tiny.cell(workload)
    got = controls.readings(cell, config, SEED, ["fp8"], _devices(cell))["fp8"]
    failed = [k for k, v in got.items() if k in cell["limits"] and v > cell["limits"][k]]
    assert failed, got


def test_traced_run_reads_the_host_side_metrics(tmp_path):
    """A CPU trace has no device plane, so the trace readers find nothing
    and say so; the counters and clocks still read."""
    got = _measure("hop-h1024.online-steady", trace=True)
    r = got["run"]
    r.peaks = {"bf16_flops_per_s": 197e12}
    assert r.trace is not None and any(s[2] == "bench/window" for s in r.trace.spans)
    assert not r.trace.devices
    assert run.load_module("metrics", "device_idle_share").read(r) is None
    blocked = run.load_module("metrics", "producer_blocked_share").read(r)
    assert 0 < blocked <= 100
    assert run.load_module("metrics", "compiles_in_window").read(r) == 0
    assert run.load_module("metrics", "step_mfu").read(r) is None
