"""The comparison that decides ``correct``: what the timed path produced
in its first steps against what the plain reference makes of the same
seed, each number beside its limit (a cell's ``limits``).

Norms are compared leaf by leaf and the worst leaf is the number: the gap
between the program's norm and the reference's, over the reference's norm
of that leaf or of the median leaf, whichever is larger.
"""

from __future__ import annotations

import statistics
from typing import Dict, List

import numpy as np

# A leaf whose first gradient is under this share of the median leaf's is
# nought to rounding in the reference; under Adam it moves by round-off
# alone, so its change is not compared.
QUIET_LEAF = 1e-3


def flatten(tree, prefix: str = "") -> Dict[str, np.ndarray]:
    out = {}
    for k in sorted(tree):
        v = tree[k]
        name = f"{prefix}/{k}" if prefix else k
        if hasattr(v, "items"):
            out.update(flatten(v, name))
        else:
            out[name] = np.asarray(v)
    return out


# A leaf whose gradient is under this share of the median leaf's is zero
# but for round-off (a key's bias under softmax): its second moment is the
# square of noise and is not compared.
SILENT_LEAF = 1e-4

def adam_moments(opt_state):
    """The Adam state (first and second moments) out of an optax state,
    wherever it sits."""
    import jax

    found = [
        s for s in jax.tree_util.tree_leaves(opt_state, is_leaf=lambda x: hasattr(x, "mu"))
        if hasattr(s, "mu")
    ]
    if len(found) != 1:
        raise ValueError(f"expected one Adam state, found {len(found)}")
    return found[0]


def program_readings(losses, params0, params, opt_state) -> dict:
    adam = adam_moments(opt_state)
    p0, p1, mu, nu = flatten(params0), flatten(params), flatten(adam.mu), flatten(adam.nu)
    if not (set(p0) == set(p1) == set(mu)):
        raise ValueError("parameter trees differ")
    norm = lambda a: float(np.linalg.norm(np.asarray(a, np.float64)))
    return {
        "losses": [float(x) for x in losses],
        "moment": mu,
        "moment_norm": {k: norm(mu[k]) for k in p1},
        "second_moment_norm": {k: norm(nu[k]) for k in p1},
        "change_norm": {k: norm(p1[k].astype(np.float64) - p0[k]) for k in p1},
    }


def _worst_leaf(prog: Dict[str, float], ref: Dict[str, float], leaves) -> float:
    floor = statistics.median(ref[k] for k in leaves)
    return max(abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30) for k in leaves)


def by_leaf(prog: dict, ref: dict) -> Dict[str, dict]:
    """Each leaf's readings, for looking into a number that reads far off."""
    out = {}
    for k in sorted(ref["moment_norm"]):
        row = {
            "grad": ref["first_grad_norm"][k],
            "moment": (prog["moment_norm"][k], ref["moment_norm"][k]),
            "change": (prog["change_norm"][k], ref["change_norm"][k]),
            "second_moment": (prog["second_moment_norm"][k], ref["second_moment_norm"][k]),
        }
        if "moment" in prog and "moment" in ref:
            row["moment_diff"] = float(
                np.linalg.norm(np.asarray(prog["moment"][k], np.float64) - ref["moment"][k])
            )
        out[k] = row
    return out


def compare(prog: dict, ref: dict, limits: Dict[str, float], extra: dict = None) -> List[dict]:
    """[{name, value, limit}] for every number the cell's ``limits`` names.
    ``extra`` is the window's own readings; a pair under ``repeat_mae`` (a
    job's held-out error before the window and at its end) is compared as
    ``repeat_gap``."""
    ref_losses = ref["losses"]
    if len(prog["losses"]) == 1:
        # A scanned dispatch hands back only the mean of its steps' losses.
        ref_losses = [statistics.fmean(ref_losses)]
    if len(prog["losses"]) != len(ref_losses):
        raise ValueError("the program and the reference report different steps")
    if set(prog["moment_norm"]) != set(ref["moment_norm"]):
        raise ValueError(
            "the program and the reference hold different leaves: "
            f"{sorted(set(prog['moment_norm']) ^ set(ref['moment_norm']))}"
        )
    leaves = sorted(ref["moment_norm"])
    g = ref["first_grad_norm"]
    loud = [k for k in leaves if g[k] >= QUIET_LEAF * statistics.median(g.values())]
    numbers = {
        "loss_gap": max(
            abs(a - b) / max(abs(b), 1e-30) for a, b in zip(prog["losses"], ref_losses)
        ),
        "moment_gap": _worst_leaf(prog["moment_norm"], ref["moment_norm"], leaves),
        "change_gap": _worst_leaf(prog["change_norm"], ref["change_norm"], loud),
    }
    if "second_moment_norm" in prog and "second_moment_norm" in ref:
        # Against the leaf's own norm: second moments of different leaves
        # are not of one scale.  What it sees and the gaps above do not: how
        # many rows stood behind a node's gradient (a halved batch leaves the
        # mean alone and raises the mean square).
        heard = [k for k in leaves if g[k] >= SILENT_LEAF * statistics.median(g.values())]
        a, b = prog["second_moment_norm"], ref["second_moment_norm"]
        numbers["second_moment_gap"] = max(
            abs(a[k] - b[k]) / max(b[k], 1e-300) for k in heard
        )
    if "moment" in prog and "moment" in ref:
        # The norm of the difference, where the gap of the norms cannot see
        # a gradient that is as long and points elsewhere.
        floor = statistics.median(ref["moment_norm"].values())
        diffs = [
            float(np.linalg.norm(np.asarray(prog["moment"][k], np.float64) - ref["moment"][k]))
            / max(ref["moment_norm"][k], floor, 1e-30)
            for k in leaves
        ]
        numbers["moment_diff"] = max(diffs)
        numbers["moment_diff_median"] = statistics.median(diffs)
    if extra and "repeat_mae" in extra:
        a, b = extra["repeat_mae"]
        numbers["repeat_gap"] = abs(a - b) / max(abs(a), 1e-30)
    unknown = set(limits) - set(numbers)
    if unknown:
        raise ValueError(f"no such compared number: {sorted(unknown)}")
    return [
        {"name": k, "value": float(numbers[k]), "limit": float(limits[k])}
        for k in sorted(limits)
    ]


def verdict(rows: List[dict]) -> bool:
    return all(np.isfinite(r["value"]) and r["value"] <= r["limit"] for r in rows)
