"""The routed experts' grouped products against the chip's roofline: the
least time the window's counted slots need (operations over the bf16 peak
or bytes over the HBM peak, whichever is larger; both from the shapes and
the slots the steps counted, by the configuration's own functions, the
same whatever implements the product) over the device time of the scope
``stream/moe/experts``, which holds the recomputed forward too."""


def read(run):
    from benchmark import run as bench
    from benchmark.reduce import stream_scopes

    got = stream_scopes.seconds(run)
    loads = stream_scopes.window_dispatches(run, "moe_load_max", "moe_load_mean")
    module = bench.load_module("configs", run.cell["config"])
    if got is None or not got.get("moe/experts") or not loads or not hasattr(module, "expert_flops"):
        return None
    m = run.config["model"]
    layers = m["num_hidden_layers"]
    slots = sum(mean for _, mean in loads) * layers * m["num_experts_held"]
    launches = len(loads) * run.window.extras.get("steps_per_launch", 1) * layers
    least = max(
        module.expert_flops(m, slots) / run.peaks["bf16_flops_per_s"],
        module.expert_bytes(m, slots, launches) / run.peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / got["moe/experts"]
