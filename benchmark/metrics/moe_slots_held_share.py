"""Token-slots routed to an expert this chip holds, of all the slots the
expert layers routed, over the window's dispatches (``trainer/dispatch``'s
``moe_slots_held`` and ``moe_slots_routed``, which the ledger sets from the
step's own count when it sees the dispatch finished; the counters
``trainer_moe_slots_*_total`` hold the same since the process began, the
warm-up with it): 6.25 expected of 32 experts in 512 under an even router."""


def read(run):
    from benchmark.reduce import stream_scopes

    slots = stream_scopes.window_dispatches(run, "moe_slots_held", "moe_slots_routed")
    routed = sum(r for _, r in slots)
    return 100.0 * sum(h for h, _ in slots) / routed if routed else None
