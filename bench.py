"""Benchmark: flagship ranker training records/sec/chip (BASELINE.md headline).

Runs on a TPU only.  Without one it exits non-zero and prints no result:
a number from a CPU run is never written under the device metric's name.

Flagship = the hop-feature parent-peer ranker (models/hop.py): neighbor
aggregation precomputed per graph snapshot, train step is pure dense MXU
work on edge batches.  Chosen over the round-1 GAT flagship on quality
at the identical config[2] workload (val log-MAE 0.505 hop vs 0.514 GAT,
tools/ablate_rankers.py) with no neighbor-gather scatter in the step's
backward pass.

Flagship WIDTH = hidden 1024 (tools/ablate_width.py, dropout ON, exact
config[2] workload): val log-MAE 0.5050 / F1 0.7964 at hidden 1024 vs
0.5067 / 0.7943 at hidden 128 — the compute-bound width is no worse on
quality and is the one the MXU can be kept busy with.

vs_baseline is measured against the north-star requirement
(BASELINE.json): 1B records / 10 min on v5e-16 ⇒ ~104,167 records/sec/chip.
The reference itself publishes no numbers (its trainer is a stub —
trainer/training/training.go:82-99), so the north-star rate is the bar.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"step_ms", "mfu", "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

# North star: 1e9 records / 600 s / 16 chips.
BASELINE_RECORDS_PER_SEC_PER_CHIP = 1e9 / 600.0 / 16.0

# bf16 peak FLOP/s of one chip, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16).  A
# device that is not in the table is an error, never a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def peak_bf16_flops(device_kind: str) -> float:
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s on record for device kind {device_kind!r}; "
            f"add it to bench.PEAK_BF16_FLOPS with its source"
        ) from None


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # A number from a CPU run must never appear under the device
        # metric's name: no chip, no result line, non-zero exit.
        print(
            f"bench: needs a TPU, found platform {dev.platform!r} "
            f"({dev.device_kind}); nothing measured",
            file=sys.stderr,
        )
        return 1
    peak_bf16_flops(dev.device_kind)  # unknown kind fails before any work
    from dragonfly2_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    _run_benchmark(jax)
    return 0


def _run_benchmark(jax) -> None:
    # TPU-native PRNG for the dropout masks: rbg is the hardware
    # generator, threefry computes its bits on the vector units.  Quality
    # holds under it — config[2] ablation at h1024: val MAE 0.5058/F1
    # 0.7959 (rbg) vs 0.5050/0.7964 (threefry), tools/ablate_width.py
    # under JAX_DEFAULT_PRNG_IMPL.  Its share of the step, measured
    # through the trainer's online path, which draws threefry (PERF.md
    # §5-6, PR 26, one v5e chip): 63 ms of 170.9 at batch 524,288 while
    # the draw was recomputed inside the six Dense_1 fusions, 23.2 of
    # 125.7 (18.5%) since the masks are drawn once and kept
    # (models/hop.py KeptMaskDropout); at this file's batch 5.79 ms of
    # 30.79 there, against 27.52 for this whole step under rbg.
    jax.config.update("jax_default_prng_impl", "rbg")
    import jax.numpy as jnp

    from dragonfly2_tpu.models import (
        HopConfig,
        HopRanker,
        build_neighbor_table,
        precompute_hop_features,
    )
    from dragonfly2_tpu.parallel.mesh import batch_sharding, create_mesh, replicated
    from dragonfly2_tpu.records.synthetic import SyntheticCluster
    from dragonfly2_tpu.trainer.train import (
        TrainConfig,
        TrainState,
        _graph_train_step,
        _make_optimizer,
    )

    n_devices = len(jax.devices())

    # Workload at the north-star's shape: 100k-node probe graph (BASELINE
    # "1B records over a 100k-node peer graph"), K=16 neighbors, 128k-edge
    # batches.
    n_nodes = 100_000
    batch = 131_072
    cluster = SyntheticCluster(num_hosts=n_nodes, seed=0)
    avg_degree = 16
    density = avg_degree / max(n_nodes - 1, 1)
    src, dst, rtt = cluster.probe_edges(density=density, seed=0)
    table = build_neighbor_table(n_nodes, src, dst, rtt / 1e9, max_neighbors=16)
    node_feats = jnp.asarray(cluster._host_feature_matrix())

    # Production flagship config: hidden 1024 (quality-validated width,
    # see module docstring), 2 hops, embed 32, dropout ON.
    mcfg = HopConfig(hidden=1024)
    hop_feats = jax.jit(lambda nf, t: precompute_hop_features(nf, t, hops=mcfg.hops))(
        node_feats, table
    )

    rng = np.random.default_rng(0)
    e_src = rng.integers(0, n_nodes, batch).astype(np.int32)
    e_dst = (e_src + rng.integers(1, n_nodes, batch).astype(np.int32)) % n_nodes
    bw = cluster._bandwidth_vec(e_src, e_dst)
    target = np.log1p(bw).astype(np.float32)

    model = HopRanker(mcfg)
    params = model.init(
        jax.random.PRNGKey(0),
        hop_feats,
        table,
        jnp.asarray(e_src[:2]),
        jnp.asarray(e_dst[:2]),
    )["params"]
    cfg = TrainConfig()
    state = TrainState.create(
        apply_fn=model.apply,
        params=params,
        tx=_make_optimizer(cfg, 100),
        dropout_rng=jax.random.PRNGKey(1),
    )

    mesh = create_mesh()
    repl = replicated(mesh)
    data_shard = batch_sharding(mesh)
    state = jax.device_put(state, repl)
    hop_feats = jax.device_put(hop_feats, repl)
    table = jax.device_put(table, repl)

    # Chained-slope timing: N steps run INSIDE one jit via fori_loop
    # (sequentially dependent through the carried state), a scalar fetch
    # waits for the chain to finish, and the per-step time is the slope
    # between two chain lengths, so the fixed cost of one dispatch and
    # one fetch cancels out.  The fetch touches a real param so the loop
    # body survives dead-code elimination.
    from functools import partial

    @partial(jax.jit, static_argnums=(6,), in_shardings=(
        repl, repl, repl, data_shard, data_shard, data_shard
    ), out_shardings=repl)
    def run_chain(s, nf, t, a, b, y, n):
        def body(_, carry):
            new_s, _loss = _graph_train_step(carry, nf, t, a, b, y, None)
            return new_s
        final = jax.lax.fori_loop(0, n, body, s)
        return final.params["Dense_0"]["bias"][0]  # tiny sync handle

    a = jax.device_put(jnp.asarray(e_src), data_shard)
    b = jax.device_put(jnp.asarray(e_dst), data_shard)
    y = jax.device_put(jnp.asarray(target), data_shard)

    # Chain lengths sized to the step: at tens of ms a step, 40 extra
    # steps dwarf the timer's jitter and keep the bench under a minute.
    n_short, n_long = 4, 44
    float(run_chain(state, hop_feats, table, a, b, y, n_short))  # compile both
    float(run_chain(state, hop_feats, table, a, b, y, n_long))

    per_step = None
    for _ in range(3):
        t0 = time.perf_counter()
        float(run_chain(state, hop_feats, table, a, b, y, n_short))
        t_short = time.perf_counter() - t0
        t0 = time.perf_counter()
        float(run_chain(state, hop_feats, table, a, b, y, n_long))
        t_long = time.perf_counter() - t0
        est = max((t_long - t_short) / (n_long - n_short), 1e-9)
        per_step = est if per_step is None else min(per_step, est)

    records_per_sec_per_chip = batch / per_step / n_devices

    # MFU from XLA's own cost model. Cost the train step DIRECTLY (not the
    # chain): HloCostAnalysis counts a while-loop body once regardless of
    # trip count, so dividing chain flops by chain length under-reports by
    # the chain length.
    step_jit = jax.jit(
        lambda s, nf, t, aa, bb, yy: _graph_train_step(s, nf, t, aa, bb, yy, None)
    )
    cost = step_jit.lower(state, hop_feats, table, a, b, y).compile().cost_analysis()
    dev = jax.devices()[0]
    mfu = float(cost["flops"]) / per_step / peak_bf16_flops(dev.device_kind)

    out = {
        "ok": True,
        "metric": "hop_ranker_train_records_per_sec_per_chip",
        "value": round(records_per_sec_per_chip, 1),
        "unit": "records/s/chip",
        "vs_baseline": round(
            records_per_sec_per_chip / BASELINE_RECORDS_PER_SEC_PER_CHIP, 3
        ),
        "step_ms": round(per_step * 1e3, 2),
        "mfu": round(mfu, 4),
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": n_devices,
        },
    }
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
