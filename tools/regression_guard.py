"""The host-side benchmarks' regression guard.

``tools/bench_download.py``, ``bench_swarm.py`` and ``bench_qos.py`` each
compare a fresh round with the last good round of their own
``BENCH_*_r*.json`` records and flag a slide in the artifact itself.
"""

from __future__ import annotations

# A fresh round that lands more than this far below the last good round
# carries a warning.
REGRESSION_WARN_FRACTION = 0.20


def apply_regression_guard(out: dict, last_good: dict) -> dict:
    """Annotate a result line with ``last_good`` ({"round", "value", ...};
    empty when no good round exists) and a warning flag when the fresh
    value regressed more than REGRESSION_WARN_FRACTION against it — a
    silent slide should be loud in the artifact, not discovered rounds
    later."""
    if not last_good:
        return out
    out["last_good"] = last_good
    value = out.get("value")
    if value is not None and value < (1.0 - REGRESSION_WARN_FRACTION) * last_good["value"]:
        out["regression_warning"] = {
            "dropped_to": round(value / last_good["value"], 3),
            "vs_round": last_good["round"],
        }
    return out
