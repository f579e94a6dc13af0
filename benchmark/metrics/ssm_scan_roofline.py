"""The Mamba-2 layers' SSD scan against the chip's roofline: the least
time the window's records need through it (``ssd_flops`` over the bf16
peak or ``ssd_bytes`` over the HBM peak, whichever is larger, by the
configuration's own functions from the widths and the chunk: the same
whatever carries the state) over the device time of the scope
``stream/mamba/scan``, which holds the chunk's decays and scores, the loop
over chunks, the time step and the D term, forward, recomputed and
backward.  A program with no such scope, or a configuration with no
``ssd_flops``, has nothing to read."""


def read(run):
    from benchmark import run as bench
    from benchmark.reduce import stream_scopes

    got = bench.load_module("metrics", "ssm_mixer_share").scope_seconds(run, "mamba", "scan")
    records = sum(n for (n,) in stream_scopes.window_dispatches(run, "records"))
    module = bench.load_module("configs", run.cell["config"])
    if got is None or not records or not hasattr(module, "ssd_flops"):
        return None
    m = run.config["model"]
    least = max(
        module.ssd_flops(m, records) / run.peaks["bf16_flops_per_s"],
        module.ssd_bytes(m, records) / run.peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / got[0]
