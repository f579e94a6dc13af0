"""Host time to put one dispatch block together on the training thread
(concatenate and reshape): ``trainer/next_block``'s length less its
``wait_s``, mean over the calls that gave a block."""


def read(run):
    from benchmark.reduce import program_spans as ps

    got = ps.window_run(run)
    if got is None:
        return None
    blocks = [s for s in ps.named(got[1], "trainer/next_block") if s.attributes.get("records")]
    if not blocks:
        return None
    own = sum(ps.seconds(s) - s.attributes.get("wait_s", 0.0) for s in blocks)
    return 1e3 * own / len(blocks)
