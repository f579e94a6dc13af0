"""The chip bring-up surfaces, as far as a CPU can check them.

``chip_smoke.py`` and ``benchmark/run.py`` must refuse to run without a TPU (a
number from a CPU run is never written under a device metric's name); the
compile cache must be placeable from outside and otherwise sit at one fixed
path in the checkout; ``deploy/run_local.py`` must leave the accelerator
to the trainer child alone; and the smoke's stage functions must run at a
tiny size on the virtual CPU mesh, so that a chip run is never spent on a
control-flow bug.
"""

import importlib.util
import os
from pathlib import Path

import pytest

import chip_smoke
from benchmark import run as benchmark_run
from dragonfly2_tpu.records.synthetic import SyntheticCluster
from dragonfly2_tpu.utils import compile_cache

REPO = Path(__file__).resolve().parents[1]


class TestNoFallbackThatHidesTheDevice:
    def test_bench_refuses_cpu_and_prints_no_value(self, capsys):
        assert benchmark_run.main([
            "--workload", "hop-h1024.online-steady", "--seed", "0", "--seconds", "1",
        ]) != 0
        out = capsys.readouterr()
        assert out.out == ""  # no result line, so no value
        assert "needs 1 TPU chip(s)" in out.err and " x cpu " in out.err

    def test_bench_peak_of_unknown_device_is_an_error(self):
        assert benchmark_run.device_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
        with pytest.raises(ValueError, match="no peaks on record"):
            benchmark_run.device_peaks("cpu")

    def test_chip_smoke_refuses_cpu_and_runs_no_stage(self, capsys, monkeypatch):
        def no_stage(*a, **kw):
            raise AssertionError("a stage ran on the CPU")

        monkeypatch.setattr(chip_smoke, "stage_a_shipped_loop", no_stage)
        assert chip_smoke.main() != 0
        out = capsys.readouterr()
        assert out.out == ""
        assert "needs a TPU" in out.err and "'cpu'" in out.err

    def test_chip_smoke_last_line_is_the_verdict_and_nothing_else(
        self, capsys, monkeypatch, tmp_path
    ):
        # The driver reads the last line of standard output and refuses
        # any key beyond these; what the stages saw goes on the line before.
        import json

        import jax

        class FakeChip:
            platform, device_kind = "tpu", "TPU v5 lite"

            def memory_stats(self):
                return {"peak_bytes_in_use": 1}

        monkeypatch.setattr(jax, "devices", lambda *a: [FakeChip()])
        monkeypatch.setattr(
            compile_cache, "enable_compile_cache", lambda: str(tmp_path)
        )
        monkeypatch.setattr(chip_smoke, "NUM_HOSTS", 64)
        for stage in ("stage_a_shipped_loop", "stage_b_train_loop",
                      "stage_b_online", "stage_b_wire_ingest", "stage_c_kernels"):
            monkeypatch.setattr(chip_smoke, stage, lambda *a, **kw: {})
        assert chip_smoke.main() == 0
        lines = capsys.readouterr().out.splitlines()
        assert json.loads(lines[-1]) == {
            "ok": True,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        }
        report = json.loads(lines[-2].removeprefix("chip_smoke: report: "))
        assert set(report["stages"]) == {"A", "B_i", "B_ii", "B_iii", "C"}
        assert all(s["ok"] for s in report["stages"].values())


class TestHostBenchRegressionGuard:
    def test_flags_a_slide_past_a_fifth_against_the_last_good_round(self):
        # tools/bench_download.py, bench_swarm.py and bench_qos.py pass
        # the last good round of their own records.
        from tools.regression_guard import apply_regression_guard

        good = {"round": 4, "value": 100.0, "file": "BENCH_DL_r04.json"}
        ok = apply_regression_guard({"value": 95.0}, good)
        assert "regression_warning" not in ok and ok["last_good"] == good
        bad = apply_regression_guard({"value": 60.0}, good)
        assert bad["regression_warning"] == {"dropped_to": 0.6, "vs_round": 4}
        # No good round at all: the guard stays silent.
        assert apply_regression_guard({"value": 1.0}, {}) == {"value": 1.0}


class TestCompileCachePlacement:
    def test_environment_wins_and_no_code_sets_another(self, monkeypatch):
        import jax

        updates = []
        monkeypatch.setattr(jax.config, "update", lambda k, v: updates.append(k))
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert compile_cache.enable_compile_cache() == "/somewhere/else"
        assert "jax_compilation_cache_dir" not in updates

    def test_default_is_one_fixed_path_in_the_checkout(self, monkeypatch):
        import jax

        updates = {}
        monkeypatch.setattr(jax.config, "update", updates.__setitem__)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(REPO / ".jax_cache")
        assert compile_cache.compile_cache_dir() == want
        assert compile_cache.enable_compile_cache() == want
        assert updates["jax_compilation_cache_dir"] == want
        # .gitignore lists it: a cache is never committed.
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


class TestOneProcessForTheChip:
    def test_run_local_pins_cpu_on_every_child_but_the_trainer(self, monkeypatch):
        spec = importlib.util.spec_from_file_location(
            "run_local", str(REPO / "deploy" / "run_local.py")
        )
        run_local = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(run_local)
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        for child in ("manager", "manager-standby", "scheduler", "scheduler-1",
                      "seed", "daemon-a", "daemon-b", "e2e"):
            assert run_local.child_env(child)["JAX_PLATFORMS"] == "cpu", child
        trainer = run_local.child_env("trainer")
        assert trainer["JAX_PLATFORMS"] == "tpu,cpu"  # the caller's platform
        assert trainer["PYTHONPATH"] == str(REPO)


# Tiny shapes of the flagship: same code paths, seconds on the CPU.
_SIZES = dict(max_neighbors=8, hidden=64, batch=1024)


@pytest.fixture(scope="module")
def tiny_cluster():
    return SyntheticCluster(num_hosts=256, seed=0)


class TestStageBTinyOnCPU:
    # ~7 s, nearly all of it the ~120 single-operation programs that
    # train_hop_ranker's un-jitted init and validation compile at any size
    # (ROADMAP Speed 1): kept out of tier-1's time cap (-m 'not slow');
    # `pytest tests/test_chip_smoke.py` runs it before a chip run.
    @pytest.mark.slow
    def test_train_loop(self, tiny_cluster):
        with chip_smoke.CompileLog() as compiles:
            # A narrow model needs (and tolerates) a faster schedule than
            # the flagship's to move the loss in six steps.
            res = chip_smoke.stage_b_train_loop(
                tiny_cluster, compiles, learning_rate=3e-3, warmup_steps=2,
                **_SIZES,
            )
        assert res["steps"] == 6 and res["loss_last"] < res["loss_first"]

    def test_online_trainer_dispatch_refresh_checkpoint_resume(
        self, tiny_cluster, tmp_path
    ):
        with chip_smoke.CompileLog() as compiles:
            res = chip_smoke.stage_b_online(
                str(tmp_path), tiny_cluster, compiles, **_SIZES
            )
        assert res["steps"] == 12 and len(res["losses"]) == 3
        # CPU against CPU: the reference check compares a program with itself.
        assert res["forward_rel_l2_vs_cpu"] == 0.0
        assert res["devices_holding_state"] == 1
        held = res["state_bytes_per_device"]
        assert held[0] > 0 and not any(held[1:])  # no mesh: one device

    def test_layout_check_refuses_state_on_one_device(self):
        # Numbers from the four-chip run's one-device stage: idle chips
        # report ~9 MB in use on their own, which must not count as
        # holding state.
        one_device = {
            "devices_holding_state": 1,
            "state_bytes_per_device": [128_000_000, 0, 0, 0],
            "bytes_in_use_per_device": [164_399_104, 9_354_752, 9_354_752, 9_354_752],
        }
        with pytest.raises(AssertionError, match="state bytes per device"):
            chip_smoke.check_layout_fills_devices("x", one_device, 4)
        spread = {
            "devices_holding_state": 4,
            "state_bytes_per_device": [128_000_000] * 4,
            "bytes_in_use_per_device": [293_000_000, 9_354_752, 142_000_000, 142_000_000],
        }
        with pytest.raises(AssertionError, match="below the state's share"):
            chip_smoke.check_layout_fills_devices("x", spread, 4)
        spread["bytes_in_use_per_device"][1] = 142_000_000
        chip_smoke.check_layout_fills_devices("x", spread, 4)

    def test_slot_row_kernels_against_their_oracles(self):
        # Stage C's newest check, interpreted at a small block: the chip
        # runs it at [32768, 2048] through Mosaic.
        res = chip_smoke.check_slot_rows(rows=256, width=256)
        assert res == {"held_rows": 153, "add_to_xla_max": 0.0}

    def test_wire_ingest_names_its_engine(self, tiny_cluster):
        res = chip_smoke.stage_b_wire_ingest(tiny_cluster, **_SIZES)
        assert res["engine"] == ("native" if res["native_available"] else "python")
