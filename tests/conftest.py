"""Test config: virtual 8-device CPU mesh, lock witness, compile witness,
crash witness, deadlock watchdog.

Session-wide concerns live here, in load order:

1. **Lock witness** (``dragonfly2_tpu/utils/dflock.py``): installed
   BEFORE any ``dragonfly2_tpu`` import so every project lock created
   during the tier-1 run is wrapped in a recording proxy.  The module is
   bootstrapped by file path (not package import) so no package
   ``__init__`` runs — and thus no module-level lock is created — ahead
   of the install.  ``tests/test_zz_lockwitness.py`` cross-validates the
   recorded acquisition-order edges against dflint's static lock graph.
   Set ``DF_LOCK_WITNESS=0`` to disable.

2. **JAX platform**: tests run on the virtual CPU mesh
   (``--xla_force_host_platform_device_count=8``), forced below whatever
   the environment presets, and with the persistent compile cache off so
   that every run compiles the same programs
   (``cli.trainer.run`` in-process would otherwise point it at the
   checkout).  The chip is reached through ``python chip_smoke.py``.

3. **Deadlock watchdog**: the tier-1 runner wraps pytest in
   ``timeout -k 10 870``, which SIGKILLs a hung run with no diagnostics —
   a deadlock dies silently.  ``faulthandler.dump_traceback_later`` is
   armed slightly inside that budget (default 840 s, override with
   ``DF_TEST_WATCHDOG_S``; 0 disables) so a wedged test dumps every
   thread's stack to stderr BEFORE the outer timeout fires.
"""

import faulthandler
import importlib.util
import os
import sys
from pathlib import Path

_REPO = Path(__file__).resolve().parents[1]

# -- 1. lock witness (must precede any dragonfly2_tpu import) ---------------

if os.environ.get("DF_LOCK_WITNESS", "1") != "0":
    _spec = importlib.util.spec_from_file_location(
        "dragonfly2_tpu.utils.dflock",
        str(_REPO / "dragonfly2_tpu" / "utils" / "dflock.py"),
    )
    _dflock = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_dflock)
    # Register under the canonical name so later package imports reuse
    # THIS instance (and its installed witness) instead of re-executing.
    sys.modules["dragonfly2_tpu.utils.dflock"] = _dflock
    _dflock.install(str(_REPO / "dragonfly2_tpu"))

# -- 2. JAX virtual mesh ----------------------------------------------------

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)

# -- 2b. compile witness (dftrace) ------------------------------------------
# Installed AFTER jax exists but BEFORE any dragonfly2_tpu import, so every
# module-level `jax.jit(...)` in project code is created through the
# counting factory.  Bootstrapped by file path like dflock (no package
# __init__ runs ahead of the install).  tests/test_zz_compilewitness.py
# cross-validates the recorded per-creation compile counts against the
# static jit-site index (tools/dflint/tracerules.py) and the checked-in
# compile budget (tools/dflint/compile_budget.toml).
# Set DF_COMPILE_WITNESS=0 to disable.

if os.environ.get("DF_COMPILE_WITNESS", "1") != "0":
    _tspec = importlib.util.spec_from_file_location(
        "dragonfly2_tpu.utils.dftrace",
        str(_REPO / "dragonfly2_tpu" / "utils" / "dftrace.py"),
    )
    _dftrace = importlib.util.module_from_spec(_tspec)
    _tspec.loader.exec_module(_dftrace)
    sys.modules["dragonfly2_tpu.utils.dftrace"] = _dftrace
    _dftrace.install(str(_REPO / "dragonfly2_tpu"))

# -- 2c. crash witness (dfcrash) --------------------------------------------
# Installed AFTER dflock/dftrace (so the state module's import is itself
# witnessed) and BEFORE any test imports: every KVTable write the suite
# performs from project code records (namespace, caller site, method,
# rows).  tests/test_zz_crashwitness.py cross-validates the observations
# against DF014's static persistence inventory
# (tools/dflint/staterules.py) and crash-injects at the declared
# multi-row sites.  Set DF_CRASH_WITNESS=0 to disable.

if os.environ.get("DF_CRASH_WITNESS", "1") != "0":
    if str(_REPO) not in sys.path:
        sys.path.insert(0, str(_REPO))
    from dragonfly2_tpu.utils import dfcrash as _dfcrash

    _dfcrash.install(str(_REPO / "dragonfly2_tpu"))

# -- 2d. span witness (dfspan) ----------------------------------------------
# Installed alongside dfcrash: wraps Tracer.span/remote_span so every
# span OPENED from project code during the suite records its caller
# module + name.  tests/test_zz_spanwitness.py cross-validates the
# observations against DF016's REQUIRED_SPANS inventory
# (tools/dflint/checkers/df016_spans.py) — the runtime half of the
# span-coverage contract (DESIGN.md §21).  Set DF_SPAN_WITNESS=0 to
# disable.

if os.environ.get("DF_SPAN_WITNESS", "1") != "0":
    if str(_REPO) not in sys.path:
        sys.path.insert(0, str(_REPO))
    from dragonfly2_tpu.utils import dfspan as _dfspan

    _span_witness = _dfspan.install(str(_REPO / "dragonfly2_tpu"))
    # Under pytest-xdist each worker runs some of the files: the workers
    # of one run pool what they observed, and test_zz_spanwitness.py, on
    # whichever worker it lands, checks the pool.
    if os.environ.get("PYTEST_XDIST_WORKER") and os.environ.get("PYTEST_XDIST_TESTRUNUID"):
        import tempfile

        _span_witness.share(
            os.path.join(
                tempfile.gettempdir(),
                "dfspan-" + os.environ["PYTEST_XDIST_TESTRUNUID"],
            ),
            os.environ["PYTEST_XDIST_WORKER"],
        )

# -- 2e. determinism witness (dfdet) ----------------------------------------
# Installed last of the witnesses: patches the ambient nondeterminism
# sources (time.time/monotonic/perf_counter + _ns, os.urandom,
# uuid.uuid1/uuid4, ambient random draws) with call-site recorders and
# wraps every declared replay root (records/determinism_contracts.py)
# so the recorder is ARMED only while a root is on the stack.
# tests/test_zz_detwitness.py cross-validates the observations against
# DF018's static taint report (tools/dflint/detrules.py) and re-runs
# every root under different PYTHONHASHSEED — the runtime half of the
# replay-determinism contract (DESIGN.md §27).  Set DF_DET_WITNESS=0 to
# disable.

if os.environ.get("DF_DET_WITNESS", "1") != "0":
    if str(_REPO) not in sys.path:
        sys.path.insert(0, str(_REPO))
    from dragonfly2_tpu.utils import dfdet as _dfdet

    _dfdet.install(str(_REPO / "dragonfly2_tpu"))

# -- 2f. ABI witness (dfabi) -------------------------------------------------
# Bookkeeping-only install (the native .so is NOT built or loaded here —
# most tier-1 tests never touch native; the witness test triggers the
# lazy load itself).  tests/test_zz_abiwitness.py requires the compiled
# library's df_abi_manifest() to byte-match the canonical JSON rendered
# from records/abi_contracts.py and round-trips a sentinel FetchDone
# through df_abi_probe_fetchdone() — the runtime half of the DF020/DF021
# ABI contract (DESIGN.md §30).  Set DF_ABI_WITNESS=0 to disable.

if os.environ.get("DF_ABI_WITNESS", "1") != "0":
    if str(_REPO) not in sys.path:
        sys.path.insert(0, str(_REPO))
    from dragonfly2_tpu.utils import dfabi as _dfabi

    _dfabi.install(str(_REPO / "dragonfly2_tpu"))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# -- 3. faulthandler deadlock watchdog --------------------------------------

_WATCHDOG_S = float(os.environ.get("DF_TEST_WATCHDOG_S", "840"))


def pytest_sessionstart(session):
    if _WATCHDOG_S > 0:
        # exit=False: dump all thread stacks, then leave the outer
        # `timeout -k` to deliver the kill — the dump is the diagnosis,
        # the runner stays the executioner.
        faulthandler.dump_traceback_later(_WATCHDOG_S, exit=False)


def pytest_sessionfinish(session, exitstatus):
    if _WATCHDOG_S > 0:
        faulthandler.cancel_dump_traceback_later()
    # Budget-calibration aid: DF_COMPILE_OBSERVED=<path> dumps the compile
    # witness's per-site stats as JSON at session end (docs: DESIGN.md §17).
    out_path = os.environ.get("DF_COMPILE_OBSERVED")
    if out_path:
        try:
            from dragonfly2_tpu.utils import dftrace

            w = dftrace.witness()
            if w is not None:
                import json

                with open(out_path, "w", encoding="utf-8") as f:
                    json.dump(
                        {
                            f"{site[0]}:{site[1]}": stats
                            for site, stats in sorted(w.snapshot().items())
                        },
                        f, indent=2, sort_keys=True,
                    )
        except Exception as exc:  # noqa: BLE001 — diagnostics-only dump
            print(f"DF_COMPILE_OBSERVED dump failed: {exc}", file=sys.stderr)


@pytest.fixture(scope="session")
def cluster():
    from dragonfly2_tpu.records.synthetic import SyntheticCluster

    return SyntheticCluster(num_hosts=48, seed=42)


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
