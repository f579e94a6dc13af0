"""Runtime span witness: dynamic validation of DF016's span inventory.

``tools/dflint/checkers/df016_spans.py`` pins each instrumented module
to the span names it must open (static AST extraction).  Static checks
can rot without failing anything: a span site the extractor cannot see
(opened through an alias it doesn't recognize) silently leaves the
inventory unenforced, and an inventoried span whose call path the suite
no longer reaches may be "present" in the AST while never actually
recording.  This module closes the loop in the lock/compile/crash
witness mould (utils/dflock.py, utils/dftrace.py, utils/dfcrash.py):

- installed by ``tests/conftest.py`` before any test runs, it wraps
  ``Tracer.span`` / ``Tracer.remote_span`` so every span OPENED from
  project code during the tier-1 run records
  ``(caller relpath, span name, kind)``;
- ``tests/test_zz_spanwitness.py`` then cross-validates: every
  inventoried site of every module the suite imported must have been
  observed at runtime (deleting a ``remote_span`` fails HERE as well as
  in the static rule), and every observed span must match a site the
  static extractor found in its module (an unmatched observation means
  the extractor has a blind spot — test failure, not silent rot).

Design constraints (mirroring dflock/dftrace/dfcrash):

- **foreign spans untouched** — only call sites whose frame lives under
  the package root record; tests and tools construct spans freely;
- **tracing.py's own frames are skipped** — ``remote_span`` delegates to
  ``span`` internally; recording that inner call would attribute every
  remote span to utils/tracing.py instead of its real opener;
- **recording failure never breaks tracing** — bookkeeping is wrapped
  defensively and the real contextmanager is always returned;
- the bookkeeping lock comes from dflock's REAL factory: diagnostics
  must not instrument diagnostics;
- **one run, several processes** — under pytest-xdist each worker sees
  only the spans of the files it ran, so a worker ``share()``s its
  observations (one appended line per NEW site) in a directory keyed by
  the run, and the cross-validation reads the union (``read_shared``).

Set ``DF_SPAN_WITNESS=0`` to disable.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Dict, Optional, Set, Tuple

Site = Tuple[str, str, str]   # (caller relpath, span name, kind)


def _raw_lock():
    try:
        from .dflock import _REAL_LOCK

        return _REAL_LOCK()
    except ImportError:  # pragma: no cover — dflock always ships
        return threading.Lock()


class SpanWitness:
    """Global recorder shared by the patched tracer methods."""

    def __init__(self, package_dir: str) -> None:
        self.package_dir = os.path.abspath(package_dir)
        self.repo_root = os.path.dirname(self.package_dir)
        self._mu = _raw_lock()
        self.observed: Dict[Site, int] = {}
        self.shared_dir: Optional[str] = None
        self._shared_file: Optional[str] = None

    def share(self, directory: str, worker: str) -> None:
        """Append every newly observed site to ``<directory>/<worker>.tsv``
        from now on (sites seen before the call included)."""
        os.makedirs(directory, exist_ok=True)
        with self._mu:
            self.shared_dir = directory
            self._shared_file = os.path.join(directory, f"{worker}.tsv")
            for key in self.observed:
                self._append(key)

    def _append(self, key: Site) -> None:
        # One short O_APPEND write a site: whole lines, whoever reads.
        with open(self._shared_file, "a", encoding="utf-8") as f:
            f.write("\t".join(key) + "\n")

    def note(self, frame, name: str, kind: str) -> None:
        filename = os.path.abspath(frame.f_code.co_filename)
        if not filename.startswith(self.package_dir + os.sep):
            return
        rel = os.path.relpath(filename, self.repo_root).replace(os.sep, "/")
        if rel == "dragonfly2_tpu/utils/tracing.py":
            # remote_span's internal self.span() call — the outer
            # wrapper already recorded the real opener.
            return
        key = (rel, name, kind)
        with self._mu:
            if key not in self.observed and self._shared_file is not None:
                self._append(key)
            self.observed[key] = self.observed.get(key, 0) + 1

    def snapshot(self) -> Dict[Site, int]:
        with self._mu:
            return dict(self.observed)

    def names_by_module(self) -> Dict[str, set]:
        """Span names by module: this process's, and every sharing
        worker's of the same run."""
        with self._mu:
            sites = set(self.observed)
        sites |= read_shared(self.shared_dir)
        out: Dict[str, set] = {}
        for (rel, name, _kind) in sites:
            out.setdefault(rel, set()).add(name)
        return out

    def reset(self) -> None:
        with self._mu:
            self.observed.clear()


def read_shared(directory: Optional[str]) -> Set[Site]:
    """The sites every worker of the run has shared so far."""
    sites: Set[Site] = set()
    if directory is None or not os.path.isdir(directory):
        return sites
    for entry in os.listdir(directory):
        with open(os.path.join(directory, entry), encoding="utf-8") as f:
            for line in f:
                parts = line.rstrip("\n").split("\t")
                if len(parts) == 3:
                    sites.add((parts[0], parts[1], parts[2]))
    return sites


_installed: Optional[SpanWitness] = None


def witness() -> Optional[SpanWitness]:
    return _installed


def _default_package_dir() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def install(package_dir: Optional[str] = None) -> SpanWitness:
    """Wrap ``Tracer.span``/``Tracer.remote_span`` with recording
    shims.  Idempotent; returns the active witness."""
    global _installed
    if _installed is not None:
        return _installed
    from .tracing import Tracer

    w = SpanWitness(package_dir or _default_package_dir())
    real_span = Tracer.span
    real_remote = Tracer.remote_span

    def span(self, name, **kwargs):
        try:
            w.note(sys._getframe(1), name, "span")
        except Exception:  # dflint: disable=DF001 — diagnostics-only bookkeeping; tracing itself must proceed
            pass
        return real_span(self, name, **kwargs)

    def remote_span(self, name, traceparent, **kwargs):
        try:
            w.note(sys._getframe(1), name, "remote_span")
        except Exception:  # dflint: disable=DF001 — diagnostics-only bookkeeping; tracing itself must proceed
            pass
        return real_remote(self, name, traceparent, **kwargs)

    Tracer.span = span
    Tracer.remote_span = remote_span
    _installed = w
    return w
