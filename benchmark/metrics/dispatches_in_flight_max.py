"""The most dispatches the host had enqueued and not yet seen finished on
the device at once, as ``run()`` counted them (``trainer/run``'s
``in_flight_max``): how far the host ran ahead of the chip."""


def read(run):
    from benchmark.reduce import program_spans as ps

    got = ps.window_run(run)
    if got is None or "in_flight_max" not in got[0].attributes:
        return None
    return float(got[0].attributes["in_flight_max"])
