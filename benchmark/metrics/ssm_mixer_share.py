"""The Mamba-2 layers (scope ``stream/mamba/``: their projections, conv,
SSD scan and gated norm, forward, recomputed and backward) as a share of
the device's busy time.  Each operation is joined by its instruction to
the train dispatch's compiled text, as ``step_unscoped_share`` joins them,
and counted where its ``op_name`` holds the scope.  A program with no such
scope has nothing to read."""


def within(op_name, *scope) -> bool:
    """Whether ``op_name``'s components, autodiff's wrappers taken off (as
    ``step_unscoped_share`` takes them off), hold ``stream`` followed by
    each of ``scope`` in turn."""
    from benchmark import run as bench

    wrapped = bench.load_module("metrics", "step_unscoped_share")._WRAPPED
    parts = [wrapped.sub("", p) for p in (op_name or "").split("/")]
    want = ("stream", *scope)
    return any(tuple(parts[i:i + len(want)]) == want for i in range(len(parts)))


def scope_seconds(run, *scope):
    """(own device seconds of the operations within ``stream/<scope>``,
    busy seconds), or None where there is nothing to read."""
    from benchmark import run as bench

    got = bench.load_module("metrics", "step_unscoped_share").own_seconds(run)
    if got is None:
        return None
    by_name, busy = got
    inside = sum(s for name, s in by_name.items() if within(name, *scope))
    return (inside, busy) if inside else None


def read(run):
    got = scope_seconds(run, "mamba")
    return None if got is None else 100.0 * got[0] / got[1]
