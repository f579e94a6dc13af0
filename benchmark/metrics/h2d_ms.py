"""Host time to hand a dispatch's three arrays to the runtime
(``trainer/h2d``: the ``jnp.asarray`` calls), mean a dispatch."""


def read(run):
    from benchmark.reduce import program_spans as ps

    got = ps.window_run(run)
    return None if got is None else ps.mean_ms(ps.named(got[1], "trainer/h2d"))
