"""What the step's own scopes leave unnamed: the device time of the
operations whose instruction in the train dispatch's compiled text names
no program scope (``stream/...``, ``loss``, ``optimizer``) or carries no
``op_name``, as a share of the device's busy time.  The tracing's own
coverage: every other per-scope share reads only what is named.

Each operation of the traced window is joined by its instruction name to
the text the driver kept (``window.extras["program_text"]``, which
``dispatch_program_text()`` gives with the grouped products' and XLA's
own copies' scopes restored since PR 36), as ``reduce/stream_scopes.py``
does, but by the whole ``op_name``: ``own_seconds`` is also what
``moe_products_share`` reads.  A run with no such text or no device plane
has nothing to read."""

import re

# A component of an op_name that is one of the step's scopes, whatever
# autodiff wrapped it in (``transpose(jvp(loss))``).
_WRAPPED = re.compile(r"^(?:[\w]+\()+|\)+$")


def scoped(op_name):
    """Whether ``op_name`` names a program scope."""
    parts = [_WRAPPED.sub("", p) for p in (op_name or "").split("/")]
    return any(
        p in ("loss", "optimizer") or (p == "stream" and i + 1 < len(parts))
        for i, p in enumerate(parts)
    )


def own_seconds(run):
    """({op_name, or None where the instruction has none: own device
    seconds on the first device}, busy seconds), kept on the window once
    read; None where there is nothing to read."""
    extras = run.window.extras
    if "op_name_s" in extras:
        return extras["op_name_s"]
    text = extras.get("program_text")
    if not text or run.trace is None or not run.trace.devices:
        return None
    from benchmark.reduce import intervals as iv
    from benchmark.tools.program_trace import instruction_scopes

    names = instruction_scopes(text)
    dev = run.trace.devices[0]
    out = {}
    for event, own in iv.self_times(dev.ops):
        op_name = names.get(event.partition(" = ")[0].strip().lstrip("%"))
        out[op_name] = out.get(op_name, 0.0) + own
    busy = iv.total(dev.busy())
    if not out or not busy:
        return None
    extras["op_name_s"] = (out, busy)
    return out, busy


def read(run):
    got = own_seconds(run)
    if got is None:
        return None
    by_name, busy = got
    return 100.0 * sum(s for name, s in by_name.items() if not scoped(name)) / busy
