"""The latent attention's four low-rank products against the chip's
peak: the least time the window's records need through them
(``latent_flops`` of the configuration's own module over the records of
the window's dispatches, forward and backward, over the bf16 peak) over
the device time of the scope ``stream/attn/latent``, which holds their
recomputed forward and their norms, RoPE and the shared key's broadcast
besides.  A program with no such scope, or a configuration with no
``latent_flops``, has nothing to read."""


def read(run):
    from benchmark import run as bench
    from benchmark.reduce import stream_scopes

    got = stream_scopes.seconds(run)
    records = sum(n for (n,) in stream_scopes.window_dispatches(run, "records"))
    module = bench.load_module("configs", run.cell["config"])
    if got is None or not got.get("attn/latent") or not records or not hasattr(module, "latent_flops"):
        return None
    least = module.latent_flops(run.config["model"], records) / run.peaks["bf16_flops_per_s"]
    return 100.0 * least / got["attn/latent"]
