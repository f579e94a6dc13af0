"""Share of ``OnlineGraphTrainer.run()`` spent waiting for input: the sum
of ``trainer/next_block``'s ``wait_s`` over the length of ``trainer/run``.
``producer_blocked_share`` seen from inside the program: near 100 where
the feed (or a driver that holds it back) sets the pace, near 0 where the
host's own work or the runtime does."""


def read(run):
    from benchmark.reduce import program_spans as ps

    got = ps.window_run(run)
    if got is None:
        return None
    root, spans = got
    waited = sum(s.attributes.get("wait_s", 0.0) for s in ps.named(spans, "trainer/next_block"))
    return 100.0 * waited / ps.seconds(root)
