"""GNN over the probe graph (the reference's ``gnn`` model type).

The reference planned "train GNN model" over network-topology probe data
(trainer/training/training.go:82-90; dataset production at
scheduler/networktopology/network_topology.go:386-497) and recorded
precision/recall/F1 GNN evaluations in the manager registry
(manager_server_v1.go:874-900), but shipped no model.  This module is the
real thing, designed for XLA rather than for a message-passing framework:

**Static-shape neighbor tables.**  Neighbor aggregation is the classic
XLA-hostility point (ragged degrees ⇒ dynamic shapes ⇒ recompiles).  We
pad/bucket every node to exactly K neighbor slots at ingest time
(``build_neighbor_table``): the model sees dense [N, K] index + mask +
edge-feature tensors, aggregation is one gather + masked mean/softmax —
pure MXU/VPU work, compiled once, trivially shardable over a mesh (node
dim on ``data``).  Degree > K: uniform subsample per epoch (GraphSAGE
semantics); degree < K: masked padding.

Models:
- ``GraphSAGE``  — mean-aggregator SAGE encoder (BASELINE configs[1]).
- ``GATRanker``  — GAT encoder + edge-score head predicting per-edge
  log-bandwidth for parent ranking (configs[2]); the scheduler's ML
  evaluator consumes its exported scores.

bf16 compute, f32 params and softmax/loss reductions.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


class NeighborTable(NamedTuple):
    """Dense, static-shape adjacency: for each node, K neighbor slots.

    indices   [N, K] int32   — neighbor node ids (0 where padded)
    mask      [N, K] float32 — 1.0 for real neighbors, 0.0 for padding
    edge_feats[N, K, E] float32 — per-edge features (normalized RTT, ...)
    """

    indices: jax.Array
    mask: jax.Array
    edge_feats: jax.Array

    @property
    def num_nodes(self) -> int:
        return self.indices.shape[0]

    @property
    def max_neighbors(self) -> int:
        return self.indices.shape[1]


def build_neighbor_table(
    n_nodes: int,
    src: np.ndarray,
    dst: np.ndarray,
    edge_feats: Optional[np.ndarray] = None,
    *,
    max_neighbors: int = 16,
    rng: Optional[np.random.Generator] = None,
) -> NeighborTable:
    """Host-side: edge lists → padded per-node neighbor slots.

    Edges are directed src→dst; the table lists, for each *dst* node, the
    src nodes probing it (in-neighbors), matching how the probe graph is
    written (prober → probed, network_topology.go Store).  Over-degree
    nodes get a uniform sample (fresh each call ⇒ per-epoch resampling).

    Fully vectorized: a random permutation of the edge list followed by a
    stable sort on dst makes "first max_neighbors per group" a uniform
    without-replacement sample — the previous per-node Python loop with
    rng.choice cost minutes per snapshot at config[5] graph scale (2^20
    nodes × K=32 ≈ 33M edges), where this is seconds.
    """
    rng = rng or np.random.default_rng(0)
    src = np.asarray(src)
    dst = np.asarray(dst)
    if edge_feats is None:
        edge_feats = np.zeros((len(src), 1), dtype=np.float32)
    edge_feats = np.asarray(edge_feats, dtype=np.float32)
    if edge_feats.ndim == 1:
        edge_feats = edge_feats[:, None]
    e_dim = edge_feats.shape[1]

    indices = np.zeros((n_nodes, max_neighbors), dtype=np.int32)
    mask = np.zeros((n_nodes, max_neighbors), dtype=np.float32)
    feats = np.zeros((n_nodes, max_neighbors, e_dim), dtype=np.float32)

    if len(src):
        # Out-of-range dst (stale/hostile ids) drop silently, exactly
        # like the old per-node loop — a negative dst would otherwise
        # python-wraparound into the LAST row as a phantom neighbor.
        in_range = (dst >= 0) & (dst < n_nodes)
        if not in_range.all():
            src, dst, edge_feats = (
                src[in_range], dst[in_range], edge_feats[in_range]
            )
    if len(src):
        perm = rng.permutation(len(src))
        order = perm[np.argsort(dst[perm], kind="stable")]
        dst_s = dst[order]
        boundaries = np.searchsorted(dst_s, np.arange(n_nodes + 1))
        pos = np.arange(len(dst_s)) - boundaries[dst_s]  # rank within group
        keep = pos < max_neighbors
        rows, cols, eid = dst_s[keep], pos[keep], order[keep]
        indices[rows, cols] = src[eid]
        mask[rows, cols] = 1.0
        feats[rows, cols] = edge_feats[eid]
    return NeighborTable(
        indices=jnp.asarray(indices),
        mask=jnp.asarray(mask),
        edge_feats=jnp.asarray(feats),
    )


@dataclass(frozen=True)
class GNNConfig:
    hidden: int = 128
    out_dim: int = 64
    num_layers: int = 2
    num_heads: int = 4          # GAT only
    edge_dim: int = 1
    # Learnable per-node embedding concatenated to the host features.
    # Host stats alone cannot encode *where* a node sits (idc/region are
    # strings the feature vector drops); the embedding learns the latent
    # position from probe-RTT supervision — the factorization that makes
    # edge-RTT/bandwidth prediction possible at all.  0 disables.
    node_embed_dim: int = 32
    dropout: float = 0.1
    dtype: jnp.dtype = jnp.bfloat16
    # Optional neighbor-gather override (ops.pallas_segment.
    # make_neighbor_gather): a custom-VJP gather whose backward
    # scatter-add runs on the MXU segment kernel.  Must be built from the
    # SAME [N, K] indices as the NeighborTable passed at call time.
    gather_fn: Optional[Callable] = None


class NodeEmbedding(nn.Module):
    """[N, D] features → [N, D + node_embed_dim] with learned identity."""

    embed_dim: int

    @nn.compact
    def __call__(self, node_feats: jax.Array) -> jax.Array:
        if self.embed_dim <= 0:
            return node_feats
        n = node_feats.shape[0]
        emb = self.param(
            "embedding",
            nn.initializers.normal(stddev=0.1),
            (n, self.embed_dim),
            jnp.float32,
        )
        return jnp.concatenate([node_feats, emb.astype(node_feats.dtype)], axis=-1)


class SAGELayer(nn.Module):
    """h' = act(W_self h ++ W_agg mean_k(h_nbr ++ e))  — one gather + matmuls."""

    width: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, h: jax.Array, table: NeighborTable) -> jax.Array:
        h = h.astype(self.dtype)
        nbr = jnp.take(h, table.indices, axis=0)          # [N, K, D]
        nbr = jnp.concatenate(
            [nbr, table.edge_feats.astype(self.dtype)], axis=-1
        )                                                  # [N, K, D+E]
        m = table.mask.astype(self.dtype)[..., None]       # [N, K, 1]
        denom = jnp.maximum(m.sum(axis=1), 1.0)            # [N, 1]
        agg = (nbr * m).sum(axis=1) / denom                # [N, D+E]
        out = jnp.concatenate(
            [
                nn.Dense(self.width, dtype=self.dtype, param_dtype=jnp.float32)(h),
                nn.Dense(self.width, dtype=self.dtype, param_dtype=jnp.float32)(agg),
            ],
            axis=-1,
        )
        return nn.gelu(
            nn.Dense(self.width, dtype=self.dtype, param_dtype=jnp.float32)(out)
        )


class GraphSAGE(nn.Module):
    """Node features [N, D] + neighbor table → embeddings [N, out_dim]."""

    config: GNNConfig = field(default_factory=GNNConfig)

    @nn.compact
    def __call__(
        self, node_feats: jax.Array, table: NeighborTable, *, train: bool = False
    ) -> jax.Array:
        cfg = self.config
        h = NodeEmbedding(cfg.node_embed_dim)(node_feats)
        for _ in range(cfg.num_layers):
            h = SAGELayer(cfg.hidden, cfg.dtype)(h, table)
            if cfg.dropout > 0:
                h = nn.Dropout(cfg.dropout, deterministic=not train)(h)
        return nn.Dense(cfg.out_dim, dtype=jnp.float32, param_dtype=jnp.float32)(h)


class GATLayer(nn.Module):
    """Multi-head attention over the K neighbor slots (masked softmax in f32)."""

    width: int          # per-head width
    num_heads: int
    dtype: jnp.dtype = jnp.bfloat16
    gather_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, h: jax.Array, table: NeighborTable) -> jax.Array:
        H, W = self.num_heads, self.width
        h = h.astype(self.dtype)
        N, K = table.indices.shape
        # Scopes name the layer's parts in a device trace (``op_name``
        # metadata only); the Dense modules keep their derived names.
        with jax.named_scope("gat/attention"):
            q = nn.Dense(H * W, dtype=self.dtype, param_dtype=jnp.float32)(h)
            q = q.reshape(N, H, W)
        # Gather the raw neighbor rows ONCE and project k/v AFTER the
        # gather: identical linear algebra, but one [N,K,D] gather (and one
        # backward scatter) instead of two — the gather traffic, not the
        # extra post-gather matmul FLOPs, dominates this layer on TPU
        # (BENCHMARKS.md lever #2; measured ~25 ms per gather+grad at
        # [100k,16,128]).  gather_fn (when set) swaps the backward
        # scatter-add for the MXU segment kernel.
        with jax.named_scope("gat/gather"):
            if self.gather_fn is not None:
                h_n = self.gather_fn(h)                            # [N, K, D]
                if h_n.shape[:2] != table.indices.shape:
                    raise ValueError(
                        f"gather_fn output {h_n.shape[:2]} does not match the "
                        f"neighbor table {table.indices.shape} — rebuild it "
                        f"with make_neighbor_gather(table.indices, ...) for "
                        f"THIS graph snapshot"
                    )
            else:
                h_n = jnp.take(h, table.indices, axis=0)           # [N, K, D]
        with jax.named_scope("gat/attention"):
            k_n = nn.Dense(H * W, dtype=self.dtype, param_dtype=jnp.float32)(h_n).reshape(
                N, K, H, W
            )
        with jax.named_scope("gat/aggregate"):
            v_n = nn.Dense(H * W, dtype=self.dtype, param_dtype=jnp.float32)(h_n).reshape(
                N, K, H, W
            )
        with jax.named_scope("gat/attention"):
            # Edge features bias the attention logit per head.
            e_bias = nn.Dense(H, dtype=self.dtype, param_dtype=jnp.float32)(
                table.edge_feats.astype(self.dtype)
            )                                                   # [N, K, H]
            logits = jnp.einsum("nhw,nkhw->nkh", q, k_n) / jnp.sqrt(
                jnp.asarray(W, dtype=self.dtype)
            )
            logits = (logits + e_bias).astype(jnp.float32)
            neg_inf = jnp.finfo(jnp.float32).min
            logits = jnp.where(table.mask[..., None] > 0, logits, neg_inf)
            attn = jax.nn.softmax(logits, axis=1)
            # Fully-padded rows: softmax over all -inf is uniform garbage → zero it.
            attn = attn * table.mask[..., None]
        with jax.named_scope("gat/aggregate"):
            out = jnp.einsum("nkh,nkhw->nhw", attn.astype(self.dtype), v_n)
            out = out.reshape(N, H * W)
            return nn.gelu(
                nn.Dense(H * W, dtype=self.dtype, param_dtype=jnp.float32)(out) + out
            )


class GATRanker(nn.Module):
    """GAT encoder + edge-score head (the parent-peer ranker).

    __call__(node_feats, table, src, dst, query_edge_feats) → [B] scores:
    predicted log-bandwidth for each queried src→dst (parent→child) edge.
    """

    config: GNNConfig = field(default_factory=GNNConfig)

    @nn.compact
    def __call__(
        self,
        node_feats: jax.Array,
        table: NeighborTable,
        src: jax.Array,           # [B] parent node ids
        dst: jax.Array,           # [B] child node ids
        query_edge_feats: Optional[jax.Array] = None,  # [B, F] transfer feats
        *,
        train: bool = False,
        return_embeddings: bool = False,
    ) -> jax.Array:
        cfg = self.config
        per_head = max(cfg.hidden // cfg.num_heads, 1)
        h = NodeEmbedding(cfg.node_embed_dim)(node_feats)
        for _ in range(cfg.num_layers):
            h = GATLayer(per_head, cfg.num_heads, cfg.dtype, cfg.gather_fn)(h, table)
            if cfg.dropout > 0:
                h = nn.Dropout(cfg.dropout, deterministic=not train)(h)
        emb = nn.Dense(cfg.out_dim, dtype=jnp.float32, param_dtype=jnp.float32)(h)
        if return_embeddings:
            # Export path: the scorer artifact stores this table and runs
            # only the head at serve time (trainer/export.py GNNScorer).
            return emb

        with jax.named_scope("gat/head"):
            s = jnp.take(emb, src, axis=0)                     # [B, out]
            d = jnp.take(emb, dst, axis=0)
            parts = [s, d, s * d]
            if query_edge_feats is not None:
                parts.append(query_edge_feats)
            x = jnp.concatenate(parts, axis=-1).astype(cfg.dtype)
            x = nn.gelu(nn.Dense(cfg.hidden, dtype=cfg.dtype, param_dtype=jnp.float32)(x))
            x = nn.gelu(nn.Dense(cfg.hidden // 2, dtype=cfg.dtype, param_dtype=jnp.float32)(x))
            return nn.Dense(1, dtype=jnp.float32, param_dtype=jnp.float32)(x)[..., 0]
