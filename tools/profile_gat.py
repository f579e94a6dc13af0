"""Profile the GAT train step on the chip: where does the step time go?

Chained-slope methodology (see bench.py): N sequentially-dependent
iterations inside one jit, scalar fetch, per-iter = slope between two
chain lengths.  Pitfalls this script works around:
- fetch must depend on EVERY carried leaf (XLA dead-tuple-element
  elimination deletes loop compute whose output isn't fetched);
- never multiply by literal 0 to build a dependency (constant-folded);
- run-to-run variance: reps, take min.

Usage (on a machine with the chip): PYTHONPATH=. python tools/profile_gat.py
"""

from __future__ import annotations

import sys
import time
from functools import partial

import numpy as np


def chain_time(fn, carry, n_short=4, n_long=16, reps=2):
    """fn(carry) -> carry (same pytree). Returns ms per call."""
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnums=(1,))
    def run(c, n):
        def body(_, cc):
            return fn(cc)
        out = jax.lax.fori_loop(0, n, body, c)
        # Touch every float leaf so nothing in the loop is DCE'd.
        tot = jnp.float32(0)
        for leaf in jax.tree_util.tree_leaves(out):
            tot = tot + leaf.reshape(-1)[0].astype(jnp.float32)
        return tot

    float(run(carry, n_short))
    float(run(carry, n_long))
    vals = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(run(carry, n_short))
        ts = time.perf_counter() - t0
        t0 = time.perf_counter()
        float(run(carry, n_long))
        tl = time.perf_counter() - t0
        vals.append((tl - ts) / (n_long - n_short) * 1e3)
    return min(vals)


def main() -> None:
    import jax

    from dragonfly2_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax.numpy as jnp

    from dragonfly2_tpu.models import GATRanker, GNNConfig, build_neighbor_table
    from dragonfly2_tpu.ops.transpose_gather import make_transpose_gather
    from dragonfly2_tpu.records.synthetic import SyntheticCluster
    from dragonfly2_tpu.trainer.train import (
        TrainConfig, TrainState, _graph_train_step, _make_optimizer,
    )

    on_tpu = jax.devices()[0].platform != "cpu"
    n_nodes = 100_000 if on_tpu else 4096
    batch = 131_072 if on_tpu else 8192
    K = 16
    D = 128
    only = sys.argv[1] if len(sys.argv) > 1 else ""

    print(f"building workload n={n_nodes} batch={batch}", flush=True)
    cluster = SyntheticCluster(num_hosts=n_nodes, seed=0)
    density = K / max(n_nodes - 1, 1)
    src, dst, rtt = cluster.probe_edges(density=density, seed=0)
    table = build_neighbor_table(n_nodes, src, dst, rtt / 1e9, max_neighbors=K)
    node_feats = jnp.asarray(cluster._host_feature_matrix())

    rng = np.random.default_rng(0)
    e_src = rng.integers(0, n_nodes, batch).astype(np.int32)
    e_dst = (e_src + rng.integers(1, n_nodes, batch).astype(np.int32)) % n_nodes
    bw = cluster._bandwidth_vec(e_src, e_dst)
    target = jnp.asarray(np.log1p(bw).astype(np.float32))
    a, b = jnp.asarray(e_src), jnp.asarray(e_dst)
    cfg = TrainConfig()

    def make_state(gnn_cfg):
        model = GATRanker(gnn_cfg)
        params = model.init(
            jax.random.PRNGKey(0), node_feats, table, a[:2], b[:2]
        )["params"]
        return TrainState.create(
            apply_fn=model.apply, params=params,
            tx=_make_optimizer(cfg, 100), dropout_rng=jax.random.PRNGKey(1),
        )

    results = {}

    def report(name, ms):
        results[name] = ms
        print(f"{name}: {ms:.1f} ms", flush=True)

    def full_step_probe(gnn_cfg):
        st = make_state(gnn_cfg)

        def step(s):
            new_s, _ = _graph_train_step(s, node_feats, table, a, b, target, None)
            return new_s
        return chain_time(step, st)

    # 1. baseline full train step
    if only in ("", "base"):
        report("full_train_step", full_step_probe(GNNConfig()))

    # 2. full train step with the scatter-free transpose gather
    if only in ("", "transpose"):
        t0 = time.perf_counter()
        tg = make_transpose_gather(
            np.asarray(table.indices), np.asarray(table.mask), n_nodes
        )
        print(f"  transpose table built in {time.perf_counter()-t0:.1f}s", flush=True)
        report("full_train_step_transpose", full_step_probe(GNNConfig(gather_fn=tg)))

    if only not in ("", "micro"):
        print(results)
        return

    # micro probes ---------------------------------------------------------
    h0 = jnp.full((n_nodes, D), 0.5, jnp.bfloat16)
    idx = table.indices

    def gather_fwd(h):
        g = jnp.take(h, idx, axis=0)
        return h + g.sum(axis=1) * jnp.bfloat16(1e-6)
    report("gather_fwd", chain_time(gather_fwd, h0))

    def gather_grad(h):
        def f(x):
            g = jnp.take(x, idx, axis=0)
            return (g.astype(jnp.float32) ** 2).sum() * 1e-9
        gr = jax.grad(f)(h)
        return h + gr.astype(h.dtype)
    report("gather_grad", chain_time(gather_grad, h0))

    # scatter-as-gather backward candidate, isolated
    from dragonfly2_tpu.ops.transpose_gather import build_transpose_table

    tt = build_transpose_table(np.asarray(idx), np.asarray(table.mask), n_nodes)
    print(f"  kout={tt.tidx.shape[1]} overflow={int(tt.over_pos.shape[0])}", flush=True)
    E = n_nodes * K
    ct0 = jnp.full((E, D), 0.25, jnp.bfloat16)
    has_spill = int(tt.over_pos.shape[0]) > 0

    def sag(ct):
        rows = jnp.take(ct, tt.tidx, axis=0)
        out = (rows * tt.tmask[..., None].astype(rows.dtype)).sum(axis=1)
        if has_spill:
            out = out.at[tt.over_dst].add(jnp.take(ct, tt.over_pos, axis=0))
        return ct + out.reshape(-1)[0] * jnp.bfloat16(1e-6)
    report("scatter_as_gather", chain_time(sag, ct0))

    # XLA segment-sum (the sort-based scatter the backward uses)
    seg_ids = jnp.asarray(np.asarray(idx).reshape(-1).astype(np.int32))

    def xla_seg(ct):
        out = jax.ops.segment_sum(
            ct.astype(jnp.float32), seg_ids, num_segments=n_nodes
        )
        return ct + out.reshape(-1)[0].astype(ct.dtype) * jnp.bfloat16(1e-6)
    report("xla_segment_sum", chain_time(xla_seg, ct0))

    # per-edge matmuls [E,D]x[D,D] x2 (the k/v denses, forward)
    w0 = jnp.full((D, D), 0.01, jnp.bfloat16)

    def edge_matmul(c):
        v, w = c
        o1 = v @ w
        o2 = v @ w
        return (v + (o1 + o2) * jnp.bfloat16(1e-6), w)
    report("edge_matmuls_2x", chain_time(edge_matmul, (ct0, w0)))

    print(results)


if __name__ == "__main__":
    main()
