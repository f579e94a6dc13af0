"""Token-slots of the busiest held expert over the mean of the held
experts, over the window's dispatches (``trainer/dispatch``'s
``moe_load_max`` and ``moe_load_mean``, which the ledger sets from the
step's own count when it sees the dispatch finished).  1.0 is even."""


def read(run):
    from benchmark.reduce import stream_scopes

    loads = stream_scopes.window_dispatches(run, "moe_load_max", "moe_load_mean")
    mean = sum(m for _, m in loads)
    return sum(x for x, _ in loads) / mean if mean else None
