"""Host time a step spends reading ``int(state.step)`` back
(``train/step_sync``): the wait for the device that the job's loop takes
every step, mean over the steps of the window's jobs."""

UNIT = "ms"     # in no cell of BENCHMARK.json yet, which would state it


def read(run):
    from benchmark.reduce import program_spans as ps

    jobs = ps.window_jobs(run)
    if not jobs:
        return None
    return ps.mean_ms([s for spans in jobs for s in ps.named(spans, "train/step_sync")])
