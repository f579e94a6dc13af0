"""What a job pays before its second step: ``train/shuffle`` (both
permutations), ``train/init`` (eager ``model.init``, the state, its
transfer) and the first ``train/step`` (trace and program load), mean over
the window's jobs."""

UNIT = "s"      # in no cell of BENCHMARK.json yet, which would state it


def read(run):
    from benchmark.reduce import program_spans as ps

    jobs = ps.window_jobs(run)
    if not jobs:
        return None
    total = 0.0
    for spans in jobs:
        steps = ps.named(spans, "train/step")
        paid = ps.named(spans, "train/shuffle") + ps.named(spans, "train/init") + steps[:1]
        total += sum(ps.seconds(s) for s in paid)
    return total / len(jobs)
