"""Hop-feature ranker: scatter-free GNN training via precomputed aggregation.

The measured wall on the GAT ranker is structural: any architecture that
gathers a per-edge [N, K, D] tensor inside the train step pays XLA's
sort-based scatter in the backward (~22 ms per layer at [100k, 16, 128]
on v5e — see BENCHMARKS.md; every scatter-avoidance rewiring measured
worse).  The TPU-native fix is to move aggregation OUT of the step
entirely, SIGN-style (Frasca et al., 2020, "SIGN: Scalable Inception
Graph Neural Networks"): neighbor aggregates of the *input* features
are parameter-independent, so they can be computed once per graph
snapshot — the gradient never flows through a gather wider than the
edge batch.

    precompute:  H = [X, A1·X, A2·(A1·X), deg, rtt-stats]   (once per snapshot)
    train step:  rows = H[src], H[dst]  (narrow endpoint gathers)
                 score = head(enc(rows_s, E[src]), enc(rows_d, E[dst]), qef)

Only the learnable per-node embedding table E still scatters in the
backward — [B, embed] with a 64-byte payload, ~10× cheaper than the
GAT's [B·K, 128] float rows.  The step is pure dense MXU work: measured
~3 ms vs the GAT's ~93 ms at the north-star shape with comparable
validation quality (BENCHMARKS.md "hop ranker" section).

Fills the same seam as models/gnn.py (the reference's stubbed trainGNN,
trainer/training/training.go:82-90); the scheduler-side scorer export
consumes it identically (trainer/export.py).
"""

from __future__ import annotations

from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .gnn import NeighborTable


@dataclass(frozen=True)
class HopConfig:
    hidden: int = 128
    out_dim: int = 64
    hops: int = 2
    node_embed_dim: int = 32
    dropout: float = 0.1
    dtype: jnp.dtype = jnp.bfloat16


def precompute_hop_features(
    node_feats: jax.Array,
    table: NeighborTable,
    *,
    hops: int = 2,
) -> jax.Array:
    """[N, D] features + neighbor table → [N, F] hop-augmented features.

    Per hop: masked-mean and inverse-RTT-weighted-mean aggregates of the
    previous hop's representation; plus degree and mean-edge-feature
    columns.  Pure jnp (one-time gathers are fine outside the step); jit
    at the call site when running per-epoch resampled tables.
    """
    x = jnp.asarray(node_feats, jnp.float32)
    return _hop_parts(
        x,
        table.mask,
        table.edge_feats,
        lambda h: jnp.take(h, table.indices, axis=0),
        hops,
    )


# THE cached jit of the replicated precompute (static hops ⇒ one traced
# program per hop count for the whole process).  Construct-per-call
# (`jax.jit(precompute_hop_features)(...)`) throws the compile cache away
# with the wrapper — dflint DF010 flags it; import this instead.
precompute_hop_features_jit = jax.jit(
    precompute_hop_features, static_argnames="hops"
)


def _hop_parts(x, mask, edge_feats, gather, hops: int) -> jax.Array:
    """THE hop-aggregation math, shared between the replicated precompute
    and the node-sharded one (parallel/graph_sharding.py) so the two stay
    numerically identical by construction.  ``gather(h) → [rows, K, D]``
    supplies each row's neighbor representations — a plain global take
    here, a halo-exchange gather in the sharded body.
    """
    m = mask.astype(jnp.float32)[..., None]               # [rows, K, 1]
    denom = jnp.maximum(m.sum(axis=1), 1.0)               # [rows, 1]
    # Inverse-RTT weights from the first edge-feature column (normalized
    # RTT at table build): nearer probes describe the node better.
    rtt = edge_feats[..., :1].astype(jnp.float32)         # [rows, K, 1]
    w = m / (1.0 + jnp.maximum(rtt, 0.0))
    w_denom = jnp.maximum(w.sum(axis=1), 1e-6)

    parts = [x]
    h = x
    for _ in range(hops):
        nbr = gather(h)                                   # [rows, K, D]
        mean_agg = (nbr * m).sum(axis=1) / denom
        wmean_agg = (nbr * w).sum(axis=1) / w_denom
        h = mean_agg
        parts.extend([mean_agg, wmean_agg])
    deg = m.sum(axis=1) / m.shape[1]                      # [rows, 1] norm degree
    mean_rtt = (rtt * m).sum(axis=1) / denom              # [rows, 1]
    parts.extend([deg, mean_rtt])
    return jnp.concatenate(parts, axis=-1)


class KeptMaskDropout(nn.Module):
    """``nn.Dropout``'s result from a mask that is drawn once and kept.

    Same key (``make_rng("dropout")`` under the module's path, so name it
    ``Dropout_<n>`` where it replaces one), same ``bernoulli``, same
    ``select``: the values and gradients are ``nn.Dropout``'s bit for bit.
    The barrier is what differs.  To XLA threefry is cheap-looking integer
    arithmetic, and it recomputes the whole draw inside every consumer of
    the mask — at [B, hidden] that was the forward matmul and both
    backward matmuls of the next Dense, three draws a call (PERF.md §6,
    PR 26).  Behind ``optimization_barrier`` the mask is one array in
    HBM, written once and read by all three.  No variables.
    """

    rate: float

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(self.make_rng("dropout"), keep, x.shape)
        mask = jax.lax.optimization_barrier(mask)
        return jax.lax.select(mask, x / keep, jnp.zeros_like(x))


class HopEncoder(nn.Module):
    """Hop features (+ learned node embedding) → node representation."""

    cfg: HopConfig
    num_nodes: int = 0

    @nn.compact
    def __call__(self, rows: jax.Array, ids: jax.Array, *, train: bool = False):
        cfg = self.cfg
        x = rows.astype(cfg.dtype)
        if cfg.node_embed_dim > 0:
            # Embedding gathers/scatters are [B, embed] — the only
            # non-dense op left in the step, with a narrow payload.
            emb = nn.Embed(
                self.num_nodes, cfg.node_embed_dim, param_dtype=jnp.float32
            )(ids)
            x = jnp.concatenate([x, emb.astype(cfg.dtype)], axis=-1)
        x = nn.gelu(nn.Dense(cfg.hidden, dtype=cfg.dtype, param_dtype=jnp.float32)(x))
        if train and cfg.dropout > 0:
            # Keeps nn.Dropout's name: the name is in the key's path.
            x = KeptMaskDropout(cfg.dropout, name="Dropout_0")(x)
        x = nn.gelu(nn.Dense(cfg.hidden, dtype=cfg.dtype, param_dtype=jnp.float32)(x))
        return nn.Dense(cfg.out_dim, dtype=jnp.float32, param_dtype=jnp.float32)(x)


class HopRanker(nn.Module):
    """Drop-in flagship ranker: same call signature as GATRanker, but
    ``node_feats`` must be the PRECOMPUTED hop features and the table is
    only consulted for its shape (aggregation already happened).

    __call__(hop_feats, table, src, dst, qef) → [B] predicted
    log-bandwidth per queried parent→child edge.
    """

    config: HopConfig

    @nn.compact
    def __call__(
        self,
        hop_feats: jax.Array,
        table: NeighborTable,
        src: jax.Array,
        dst: jax.Array,
        query_edge_feats=None,
        *,
        train: bool = False,
        return_embeddings: bool = False,
    ) -> jax.Array:
        cfg = self.config
        n = hop_feats.shape[0]
        encoder = HopEncoder(cfg, num_nodes=n)
        if return_embeddings:
            # Export path (trainer/export.py GNNScorer): embed every node.
            all_ids = jnp.arange(n, dtype=jnp.int32)
            return encoder(hop_feats, all_ids, train=False)
        # The scopes are metadata on the compiled program's operations
        # (``op_name``), forward and backward: they name the parts of the
        # step in a device trace and add, move and rename nothing (the
        # modules keep the names flax derives, which key the parameters).
        with jax.named_scope("hop/gather"):
            s_rows = jnp.take(hop_feats, src, axis=0)
            d_rows = jnp.take(hop_feats, dst, axis=0)
        with jax.named_scope("hop/src"):
            s = encoder(s_rows, src, train=train)
        with jax.named_scope("hop/dst"):
            d = encoder(d_rows, dst, train=train)
        with jax.named_scope("hop/pair"):
            parts = [s, d, s * d]
            if query_edge_feats is not None:
                parts.append(query_edge_feats)
            x = jnp.concatenate(parts, axis=-1).astype(cfg.dtype)
        with jax.named_scope("hop/head"):
            x = nn.gelu(nn.Dense(cfg.hidden, dtype=cfg.dtype, param_dtype=jnp.float32)(x))
            x = nn.gelu(
                nn.Dense(cfg.hidden // 2, dtype=cfg.dtype, param_dtype=jnp.float32)(x)
            )
            return nn.Dense(1, dtype=jnp.float32, param_dtype=jnp.float32)(x)[..., 0]
