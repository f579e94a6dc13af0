"""The benchmark: everything the driver's check measures with lives here.

``run.py`` is the one command (see ``BENCHMARK.json``).  It finds a cell in
``workloads/``, its configuration in ``configs/``, its driver in
``drivers/``, its plain reference in ``reference/`` and each per-layer
metric's reader in ``metrics/`` by name, and holds no list of any of them:
a later PR adds files and entries and edits none.

``probes/`` holds cells that ``BENCHMARK.json`` does not list: those that
wait for a later PR (each says what for under ``waits_for``) and those that
only size a cell.  ``run.py --probe 1`` runs one by hand; the driver never
does, and no claim rests on one.
"""
