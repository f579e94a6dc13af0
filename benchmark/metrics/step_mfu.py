"""The whole step's share of the chip's peak: FLOPs the forward and
backward of the traced steps need, counted from the shapes by the
configuration's own function, over the device time of the train programs
x bf16 peak, summed over the devices.  The time is ``step_device_ms``'s:
what the host does between programs is the train loop's and the entry
point's, and does not move it."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    from benchmark.reduce import xplane

    per_launch = run.window.extras.get("steps_per_launch", 1)
    need = seconds = 0.0
    for dev in run.trace.devices:
        launches = xplane.launches(dev)
        need += run.step_flops / run.chips * len(launches) * per_launch
        seconds += sum(b - a for a, b in launches)
    if not seconds:
        return None
    return 100.0 * need / (seconds * run.peaks["bf16_flops_per_s"])
