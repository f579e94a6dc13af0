"""Runtime span-witness cross-validation (DESIGN.md §21).

``zz`` prefix: runs LAST, after the suite has exercised every plane, so
the witness (``utils/dfspan.py``, installed by conftest before any test)
has seen the session's full span traffic.  Under pytest-xdist the traffic
is spread over the workers: each shares what it observed (conftest), this
file reads the pool, and because the file is handed out last but not
necessarily finished last, the coverage check waits (bounded) while
other workers are still running the files that open the missing spans.

Three directions of validation against DF016's static inventory
(``tools/dflint/checkers/df016_spans.py`` REQUIRED_SPANS):

1. **inventory staleness** — every inventoried module exists and the
   static extractor finds every inventoried site in its AST (the same
   discipline as baseline.toml / the §16 lock graph);
2. **extractor blind spots** — every span the suite OBSERVED from an
   inventoried module must match a site the static extractor found
   there: an unmatched observation means spans are being opened through
   a pattern the extractor cannot see (failure, not silent rot);
3. **runtime coverage** — every inventoried site of every module the
   suite imported must have been observed at runtime: deleting a
   ``remote_span`` (or orphaning its call path) fails HERE as well as in
   the static rule — the acceptance mutation's second half.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path
from typing import Dict, List, Set, Tuple

import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from dragonfly2_tpu.utils import dfspan  # noqa: E402
from tools.dflint.checkers.df016_spans import (  # noqa: E402
    REQUIRED_SPANS,
    site_matches,
    span_sites,
    stale_inventory_entries,
)
from tools.dflint.core import load_module  # noqa: E402

pytestmark = pytest.mark.skipif(
    dfspan.witness() is None,
    reason="span witness disabled (DF_SPAN_WITNESS=0)",
)


def _static_sites(rel: str) -> Set[str]:
    return span_sites(load_module(REPO / rel, REPO))


def _module_imported(rel: str) -> bool:
    target = str((REPO / rel).resolve())
    for mod in list(sys.modules.values()):
        f = getattr(mod, "__file__", None)
        if f and str(Path(f).resolve()) == target:
            return True
    return False


def missing_coverage(
    names_by_module: Dict[str, Set[str]], imported: Set[str]
) -> List[Tuple[str, str]]:
    """Inventoried (module, site) pairs the run did NOT observe, for
    modules the run imported.  The mutation test drives this directly
    with a doctored observation set."""
    out: List[Tuple[str, str]] = []
    for rel, sites in REQUIRED_SPANS.items():
        if rel not in imported:
            continue
        names = names_by_module.get(rel, set())
        for site in sites:
            if not any(site_matches(site, n) for n in names):
                out.append((rel, site))
    return out


# Longest the coverage check waits for other xdist workers to finish the
# files that open a span it misses; spent only where a site is missing.
POOL_WAIT_S = 300.0


def _imported_inventory() -> Set[str]:
    return {rel for rel in REQUIRED_SPANS if _module_imported(rel)}


@pytest.fixture(scope="module")
def pooled_names() -> Dict[str, Set[str]]:
    """Span names by module over every worker of the run, read once the
    pool covers the inventory or the other workers had their time."""
    witness = dfspan.witness()
    imported = _imported_inventory()
    deadline = time.monotonic() + (POOL_WAIT_S if witness.shared_dir else 0.0)
    while True:
        names = witness.names_by_module()
        if not missing_coverage(names, imported) or time.monotonic() >= deadline:
            return names
        time.sleep(0.5)


class TestSpanWitness:
    def test_inventory_not_stale(self):
        assert stale_inventory_entries(REPO) == [], (
            "REQUIRED_SPANS names modules that no longer exist — update "
            "tools/dflint/checkers/df016_spans.py"
        )

    def test_static_extractor_finds_every_inventoried_site(self):
        for rel, sites in REQUIRED_SPANS.items():
            present = _static_sites(rel)
            for site in sites:
                assert site in present, (
                    f"{rel}: inventoried span site {site!r} not found by "
                    "the static extractor — site deleted or renamed "
                    "without updating REQUIRED_SPANS"
                )

    def test_observed_spans_match_static_sites(self, pooled_names):
        """Extractor blind-spot check: a span observed at runtime from an
        inventoried module must correspond to a statically-visible
        site."""
        by_mod = pooled_names
        for rel in REQUIRED_SPANS:
            static = _static_sites(rel)
            for name in by_mod.get(rel, set()):
                assert any(site_matches(s, name) for s in static), (
                    f"{rel}: runtime span {name!r} matches no "
                    "statically-extracted site — the DF016 extractor has "
                    "a blind spot for how this span is opened"
                )

    def test_inventoried_sites_observed_at_runtime(self, pooled_names):
        """The runtime half of the DF016 acceptance bar: every
        inventoried site of every imported module was actually opened
        during this tier-1 run."""
        by_mod = pooled_names
        imported = _imported_inventory()
        # The suite certainly imports the core planes — an empty imported
        # set would make this test vacuously green.
        assert "dragonfly2_tpu/daemon/conductor.py" in imported
        assert "dragonfly2_tpu/rpc/scheduler_server.py" in imported
        missing = missing_coverage(by_mod, imported)
        assert not missing, (
            "inventoried span sites never observed at runtime (span "
            f"deleted, or its call path orphaned): {missing}"
        )

    def test_witness_catches_deleted_span_site(self, pooled_names):
        """Mutation sensitivity, runtime half: drop one module's rpc/*
        observations from the witnessed set — exactly what deleting the
        scheduler_server remote_span would produce — and the coverage
        check must name it."""
        by_mod = pooled_names
        imported = _imported_inventory()
        assert missing_coverage(by_mod, imported) == []
        doctored = {
            rel: (
                {n for n in names if not n.startswith("rpc/")}
                if rel == "dragonfly2_tpu/rpc/scheduler_server.py"
                else names
            )
            for rel, names in by_mod.items()
        }
        missing = missing_coverage(doctored, imported)
        assert ("dragonfly2_tpu/rpc/scheduler_server.py", "rpc/*") in missing
