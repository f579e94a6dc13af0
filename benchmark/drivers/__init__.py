"""One module per way of driving the trainer.  A driver has
``setup(ctx) -> session``; a session has ``first`` (what the timed path
produced in its first steps), ``reference_inputs`` (what the plain
reference needs to follow them), ``run_window(seconds)`` and
``release()``.  ``run.py`` finds the module by the cell's ``driver``."""
