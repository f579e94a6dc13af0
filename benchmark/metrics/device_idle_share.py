"""1 - union of the device's operation intervals over the window (worst
device)."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    from benchmark.reduce import intervals as iv

    busy = min(iv.total(d.busy()) for d in run.trace.devices)
    return 100.0 * (1.0 - busy / run.window.elapsed_s)
