"""BASELINE configs[2] ablation: GAT vs hop-feature ranker vs plain MLP.

Same workload for every model — 100k-node probe graph, 2M download
edges, log1p-bandwidth targets, identical split — so the comparison is
apples-to-apples:

- ``gat``  — GATRanker (models/gnn.py), the round-1 flagship;
- ``hop``  — HopRanker (models/hop.py), precomputed aggregation;
- ``mlp``  — MLPRegressor on endpoint HOST FEATURES only (no graph, no
  node identity): the ablation VERDICT r1 weak-#7 asked for — how much
  does the graph actually buy?

Usage:
  PYTHONPATH=. python tools/ablate_rankers.py [gat|hop|mlp ...]
Prints one JSON line per model.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def main() -> None:
    import jax

    from dragonfly2_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from dragonfly2_tpu.models import build_neighbor_table
    from dragonfly2_tpu.records.synthetic import SyntheticCluster
    from dragonfly2_tpu.trainer.train import (
        TrainConfig,
        train_gat_ranker,
        train_hop_ranker,
    )

    which = [a for a in sys.argv[1:] if not a.startswith("-")] or ["hop", "mlp"]
    on_tpu = jax.devices()[0].platform != "cpu"
    n_nodes = 100_000 if on_tpu else 2_000
    n_edges = 2_000_000 if on_tpu else 40_000
    epochs = 60 if on_tpu else 8

    print(f"# workload: {n_nodes} nodes, {n_edges} edges, {epochs} epochs",
          file=sys.stderr, flush=True)
    cluster = SyntheticCluster(num_hosts=n_nodes, seed=0)
    src, dst, rtt = cluster.probe_edges(density=16 / max(n_nodes - 1, 1), seed=0)
    table = build_neighbor_table(n_nodes, src, dst, rtt / 1e9, max_neighbors=16)
    nf = cluster._host_feature_matrix()

    rng = np.random.default_rng(0)
    es = rng.integers(0, n_nodes, n_edges).astype(np.int32)
    ed = (es + rng.integers(1, n_nodes, n_edges).astype(np.int32)) % n_nodes
    y = np.log1p(cluster._bandwidth_vec(es, ed)).astype(np.float32)
    mean_mae = float(np.mean(np.abs(y - y.mean())))
    cfg = TrainConfig(epochs=epochs)

    def report(name, metrics, wall, extra=None):
        out = {
            "model": name,
            "val_log_mae": round(metrics.mae, 4),
            "f1": round(metrics.f1, 4),
            "mean_predictor_mae": round(mean_mae, 4),
            "wall_s": round(wall, 1),
        }
        out.update(extra or {})
        print(json.dumps(out), flush=True)

    if "hop" in which:
        t0 = time.time()
        _, m, hist = train_hop_ranker(nf, table, es, ed, y, config=cfg)
        report("hop", m, time.time() - t0,
               {"records_per_sec": round(hist[-1]["records_per_sec"], 1) if hist else None})

    if "gat" in which:
        t0 = time.time()
        _, m, hist = train_gat_ranker(nf, table, es, ed, y, config=cfg,
                                      batch_size=131_072)
        report("gat", m, time.time() - t0,
               {"records_per_sec": round(hist[-1]["records_per_sec"], 1) if hist else None})

    if "mlp" in which:
        # No graph, no node identity: endpoint host features only — the
        # graph-value ablation.  Small bespoke loop (train_mlp is coupled
        # to the download-record column layout).
        import jax.numpy as jnp
        import optax
        from dragonfly2_tpu.models import MLPConfig, MLPRegressor
        from dragonfly2_tpu.models.mlp import warm_start_output_bias
        from dragonfly2_tpu.trainer.train import (
            _huber, _regression_metrics,
        )

        feats = np.concatenate([nf[es], nf[ed]], axis=1).astype(np.float32)
        mu, sd = feats.mean(0), np.maximum(feats.std(0), 1e-3)
        feats = (feats - mu) / sd
        split = int(len(y) * 0.9)
        t0 = time.time()
        model = MLPRegressor(MLPConfig(in_dim=feats.shape[1], dropout=0.0))
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, feats.shape[1])))["params"]
        params = warm_start_output_bias(params, float(y[:split].mean()))
        tx = optax.adamw(1e-3)
        opt = tx.init(params)

        @jax.jit
        def step(p, o, xb, yb):
            def loss_fn(pp):
                return _huber(model.apply({"params": pp}, xb), yb)
            l, g = jax.value_and_grad(loss_fn)(p)
            up, o2 = tx.update(g, o, p)
            return optax.apply_updates(p, up), o2, l

        b = 65_536
        for epoch in range(epochs):
            order = np.random.default_rng(epoch).permutation(split)
            for s0 in range(0, split - b + 1, b):
                idx = order[s0:s0 + b]
                params, opt, _ = step(
                    params, opt, jnp.asarray(feats[idx]), jnp.asarray(y[idx])
                )
        pred = np.asarray(model.apply({"params": params}, jnp.asarray(feats[split:])))
        report("mlp_hostfeats", _regression_metrics(pred, y[split:]), time.time() - t0)


if __name__ == "__main__":
    main()
