"""MFU headroom demo: the hop ranker train step at compute-bound widths.

The accuracy-optimal flagship (hidden 128) is memory-bound — its ~97
GFLOP/step would take 0.5 ms at peak, so even a perfect schedule caps
MFU at ~5% of a 10 ms step (BENCHMARKS.md roofline section).  This tool
shows the SAME train step saturating the MXU when the model is wide
enough to be FLOPs-dominated: widths 512/1024/2048 with XLA-cost-model
MFU per step.  Chained-slope timing (see bench.py).

Usage: PYTHONPATH=. python tools/mfu_wide.py
"""

from __future__ import annotations

import json
import time
from functools import partial

import numpy as np


def main() -> None:
    import jax

    from dragonfly2_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax.numpy as jnp

    from dragonfly2_tpu.models import (
        HopConfig,
        HopRanker,
        build_neighbor_table,
        precompute_hop_features,
    )
    from dragonfly2_tpu.records.synthetic import SyntheticCluster
    from dragonfly2_tpu.trainer.train import (
        TrainConfig, TrainState, _graph_train_step, _make_optimizer,
    )

    on_tpu = jax.devices()[0].platform != "cpu"
    n_nodes = 100_000 if on_tpu else 2048
    batch = 131_072 if on_tpu else 4096
    peak = 197e12 if on_tpu else 1e12

    cluster = SyntheticCluster(num_hosts=n_nodes, seed=0)
    src, dst, rtt = cluster.probe_edges(density=16 / (n_nodes - 1), seed=0)
    table = build_neighbor_table(n_nodes, src, dst, rtt / 1e9, max_neighbors=16)
    node_feats = jnp.asarray(cluster._host_feature_matrix())
    rng = np.random.default_rng(0)
    e_src = jnp.asarray(rng.integers(0, n_nodes, batch), jnp.int32)
    e_dst = jnp.asarray(rng.integers(0, n_nodes, batch), jnp.int32)
    y = jnp.asarray(rng.normal(size=batch).astype(np.float32))

    for hidden in (128, 512, 1024, 2048):
        mcfg = HopConfig(hidden=hidden, dropout=0.0)
        hop_feats = precompute_hop_features(node_feats, table, hops=mcfg.hops)
        model = HopRanker(mcfg)
        params = model.init(
            jax.random.PRNGKey(0), hop_feats, table, e_src[:2], e_dst[:2]
        )["params"]
        state = TrainState.create(
            apply_fn=model.apply, params=params,
            tx=_make_optimizer(TrainConfig(), 100),
            dropout_rng=jax.random.PRNGKey(1),
        )

        @partial(jax.jit, static_argnums=(6,))
        def chain(s, nf, t, a, b, yy, n):
            def body(_, c):
                ns, _l = _graph_train_step(c, nf, t, a, b, yy, None)
                return ns
            out = jax.lax.fori_loop(0, n, body, s)
            return out.params["Dense_0"]["bias"][0]

        n_short, n_long = (4, 24) if on_tpu else (2, 6)
        float(chain(state, hop_feats, table, e_src, e_dst, y, n_short))
        float(chain(state, hop_feats, table, e_src, e_dst, y, n_long))
        per_step = None
        for _ in range(2):
            t0 = time.perf_counter()
            float(chain(state, hop_feats, table, e_src, e_dst, y, n_short))
            ts = time.perf_counter() - t0
            t0 = time.perf_counter()
            float(chain(state, hop_feats, table, e_src, e_dst, y, n_long))
            tl = time.perf_counter() - t0
            est = (tl - ts) / (n_long - n_short)
            per_step = est if per_step is None else min(per_step, est)

        flops = None
        try:
            sj = jax.jit(lambda s, nf, t, a, b, yy: _graph_train_step(
                s, nf, t, a, b, yy, None))
            cost = sj.lower(
                state, hop_feats, table, e_src, e_dst, y
            ).compile().cost_analysis()
            flops = float(cost["flops"]) if cost and "flops" in cost else None
        except Exception:
            pass
        out = {
            "hidden": hidden,
            "step_ms": round(per_step * 1e3, 2),
            "records_per_sec": round(batch / per_step, 1),
        }
        if flops:
            out["step_gflop"] = round(flops / 1e9, 1)
            out["mfu"] = round(flops / per_step / peak, 4)
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
