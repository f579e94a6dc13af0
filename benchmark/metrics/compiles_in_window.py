"""Programs XLA compiled inside the window (persistent-cache misses seen
by ``jax.monitoring``).  A job re-traces and must load, not compile."""


def read(run):
    return float(run.compiles_in_window)
