"""Device idle between consecutive train programs of one unit of work,
summed and divided by the launches (first device)."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    from benchmark.reduce import intervals as iv
    from benchmark.reduce import xplane

    dev = run.trace.devices[0]
    launches = xplane.launches(dev)
    per = run.window.extras.get("launches_per_unit") or len(launches)
    if len(launches) < 2:
        return None
    busy = dev.busy()
    idle = sum(
        iv.total(iv.gaps(busy, launches[i][1], launches[i + 1][0]))
        for i in range(len(launches) - 1)
        if (i + 1) % per and launches[i + 1][0] > launches[i][1]
    )
    return 1e3 * idle / len(launches)
