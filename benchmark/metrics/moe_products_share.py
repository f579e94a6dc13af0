"""The routed experts' grouped products (scope
``stream/moe/experts/grouped``: the chip's ``ragged-dot`` custom calls,
forward and backward, whatever implements them) as a share of the
device's busy time.  The compiler leaves these calls without their scope;
``dispatch_program_text()`` restores it from the same ``lower()`` since
PR 36, so a program from before names none and has nothing to read."""

GROUPED = "stream/moe/experts/grouped"


def read(run):
    from benchmark import run as bench

    got = bench.load_module("metrics", "step_unscoped_share").own_seconds(run)
    if got is None:
        return None
    by_name, busy = got
    products = [s for name, s in by_name.items() if name and f"{GROUPED}/" in name]
    return 100.0 * sum(products) / busy if products else None
