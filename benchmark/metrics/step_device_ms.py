"""Device time of the train programs over the steps they ran (mean of the
devices)."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    from benchmark.reduce import xplane

    per_launch = run.window.extras.get("steps_per_launch", 1)
    times = []
    for dev in run.trace.devices:
        launches = xplane.launches(dev)
        if launches:
            times.append(sum(b - a for a, b in launches) / (len(launches) * per_launch))
    return 1e3 * sum(times) / len(times) if times else None
