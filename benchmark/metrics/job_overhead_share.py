"""Share of a job's wall time outside its train steps: 1 - (first train
program's start to the last one's end) over the job's wall, over all jobs
of the window.  Init, tracing, program load and validation live here."""

UNIT = "%"      # in no cell of BENCHMARK.json yet, which would state it


def read(run):
    walls = run.window.extras.get("unit_walls_s")
    per = run.window.extras.get("launches_per_unit")
    if run.trace is None or not run.trace.devices or not walls or not per:
        return None
    from benchmark.reduce import xplane

    launches = xplane.launches(run.trace.devices[0])
    if len(launches) != per * len(walls):
        return None
    spans = sum(
        launches[i + per - 1][1] - launches[i][0] for i in range(0, len(launches), per)
    )
    return 100.0 * (1.0 - spans / sum(walls))
