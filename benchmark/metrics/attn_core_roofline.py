"""Attention's core against the chip's peak: the least time the keys the
window's queries attended need (``attention_flops`` of the configuration's
own module over the dispatches' ``attn_keys_attended_<kind>``, which the
ledger sets from the step's own count: scores and weighted values forward
and the four products backward, over the bf16 peak) over the device time
of the scope ``stream/attn/core``, which holds the recomputed forward and
what a band run by position computes and masks away.  A program that
counts no keys, or a configuration with no ``attention_flops``, has
nothing to read."""

KINDS = ("window", "full")


def keys(run, name: str) -> float:
    """``trainer/dispatch``'s ``attn_keys_<name>_<kind>`` summed over the
    window's dispatches and the layer kinds that have one."""
    from benchmark.reduce import stream_scopes

    return float(sum(
        sum(v for (v,) in stream_scopes.window_dispatches(run, f"attn_keys_{name}_{kind}"))
        for kind in KINDS
    ))


def read(run):
    from benchmark import run as bench
    from benchmark.reduce import stream_scopes

    got = stream_scopes.seconds(run)
    attended = keys(run, "attended")
    module = bench.load_module("configs", run.cell["config"])
    if got is None or not got.get("attn/core") or not attended or not hasattr(module, "attention_flops"):
        return None
    least = module.attention_flops(run.config["model"], attended) / run.peaks["bf16_flops_per_s"]
    return 100.0 * least / got["attn/core"]
