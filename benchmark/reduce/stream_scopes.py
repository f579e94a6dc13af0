"""Device time of the stream ranker's step by its own scopes
(``stream/gdn/scan``, ``stream/moe/experts``, ...): each operation of the
traced window is joined by its instruction name to the train dispatch's
compiled text, which the driver kept (``window.extras["program_text"]``),
as ``tools/program_trace.py`` does by hand, and the ``op_name`` found there
is cut down to the scope.  Forward, recomputed and backward alike.  A run
with no such text, no device plane or no such scope has nothing to read.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

from . import intervals as iv

_SCOPE = re.compile(r"(?:^|/)stream/((?:gdn|attn|moe)/[a-z]+|embed|head)(?=/|$)")
MOE = ("moe/router", "moe/dispatch", "moe/experts", "moe/shared", "moe/combine")


def scope_of(op_name: str) -> Optional[str]:
    found = _SCOPE.search(op_name)
    return found.group(1) if found else None


def seconds(run) -> Optional[Dict[str, float]]:
    """{scope: own device seconds on the first device}, with ``busy`` the
    device's busy time; kept on the window once read."""
    extras = run.window.extras
    if "stream_scope_s" in extras:
        return extras["stream_scope_s"]
    text = extras.get("program_text")
    if not text or run.trace is None or not run.trace.devices:
        return None
    from benchmark.tools.program_trace import instruction_scopes

    names = instruction_scopes(text)
    dev = run.trace.devices[0]
    out: Dict[str, float] = {}
    for event, own in iv.self_times(dev.ops):
        instruction = event.partition(" = ")[0].strip().lstrip("%")
        scope = scope_of(names.get(instruction, ""))
        if scope is not None:
            out[scope] = out.get(scope, 0.0) + own
    if not out:
        return None
    out["busy"] = iv.total(dev.busy())
    extras["stream_scope_s"] = out
    return out


def share(run, scopes) -> Optional[float]:
    got = seconds(run)
    if got is None or not got["busy"]:
        return None
    return 100.0 * sum(got.get(s, 0.0) for s in scopes) / got["busy"]


def window_dispatches(run, *names):
    """[(value of each of ``names``)] of the window's dispatches, from the
    attributes the ledger put on their ``trainer/dispatch`` spans when it
    saw them finished; a dispatch that lacks one is left out."""
    from . import program_spans as ps

    got = ps.window_run(run)
    if got is None:
        return []
    return [
        tuple(s.attributes[n] for n in names)
        for s in ps.named(got[1], "trainer/dispatch") if all(n in s.attributes for n in names)
    ]
