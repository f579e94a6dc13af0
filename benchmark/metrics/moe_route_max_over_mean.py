"""Token-slots routed to the busiest expert of the busiest expert layer
over the mean, all the experts counted, held here or not, over the
window's dispatches (``trainer/dispatch``'s ``moe_route_max`` and
``moe_route_mean``, which the ledger sets from the step's own count where
the expert layers hold a selection bias).  1.0 is even: what the bias
balances.  A program that counts no such loads has nothing to read."""


def read(run):
    from benchmark.reduce import stream_scopes

    loads = stream_scopes.window_dispatches(run, "moe_route_max", "moe_route_mean")
    mean = sum(m for _, m in loads)
    return sum(x for x, _ in loads) / mean if mean else None
