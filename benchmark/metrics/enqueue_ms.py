"""Host time inside the train program's call (``trainer/enqueue``), mean a
dispatch.  The call is asynchronous: it rises to the dispatch's device
time when the runtime, not the trainer, holds the host back."""


def read(run):
    from benchmark.reduce import program_spans as ps

    got = ps.window_run(run)
    return None if got is None else ps.mean_ms(ps.named(got[1], "trainer/enqueue"))
