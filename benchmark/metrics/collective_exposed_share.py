"""Share of the window in which a collective operation ran on a device
while no compute ran there (worst device).  Nothing to read where the
trace holds no collective."""

UNIT = "%"      # in no cell of BENCHMARK.json yet, which would state it


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    from benchmark.reduce import intervals as iv
    from benchmark.reduce import xplane

    worst = None
    for dev in run.trace.devices:
        leaves = iv.leaves(dev.ops)
        coll = [(a, b) for a, b, n in leaves if xplane.is_collective(n)]
        if not coll:
            continue
        cover = [(a, b) for a, b, n in leaves if not xplane.is_collective(n)]
        share = 100.0 * iv.exposed(coll, cover) / run.window.elapsed_s
        worst = share if worst is None else max(worst, share)
    return worst
