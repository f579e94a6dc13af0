"""Share of the window the producer spent blocked in
``feed_downloads(block=True)``.  Near 100: the trainer sets the pace.
Near 0: the feed does, and the rate is the generator's."""


def read(run):
    blocked = run.window.extras.get("producer_blocked_s")
    if blocked is None:
        return None
    return 100.0 * blocked / run.window.elapsed_s
