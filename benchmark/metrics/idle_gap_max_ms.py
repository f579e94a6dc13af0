"""The longest interval in which no operation ran on a device, between the
first and the last operation of the traced window (worst device)."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    from benchmark.reduce import intervals as iv
    from benchmark.reduce import xplane

    lo, hi = xplane.bounds(run.trace)
    longest = [
        max((b - a for a, b in iv.gaps(d.busy(), lo, hi)), default=0.0)
        for d in run.trace.devices
    ]
    return 1e3 * max(longest)
