"""``gat-c2``: how the program is told this configuration, and the
operations one train step needs, from the shapes."""

from __future__ import annotations


def model_config(m: dict):
    import jax.numpy as jnp

    from dragonfly2_tpu.models.gnn import GNNConfig

    return GNNConfig(
        hidden=m["hidden"], out_dim=m["out_dim"], num_layers=m["num_layers"],
        num_heads=m["num_heads"], edge_dim=m["edge_dim"],
        node_embed_dim=m["node_embed_dim"], dropout=m["dropout"],
        dtype=jnp.dtype(m["dtype"]),
    )


def batch_job(m: dict, node_feats, table):
    from dragonfly2_tpu.trainer.train import train_gat_ranker

    return train_gat_ranker, node_feats, {}


def macs_per_node(m: dict, feat_dim: int, k: int) -> int:
    """One node's share of encoding the whole graph, forward."""
    h, heads = m["hidden"], m["num_heads"]
    d_in, total = feat_dim + m["node_embed_dim"], 0
    for _ in range(m["num_layers"]):
        total += d_in * h                  # queries
        total += 2 * k * d_in * h          # keys and values, after the gather
        total += k * m["edge_dim"] * heads # edge bias
        total += 2 * k * h                 # logits, weighted sum
        total += h * h                     # output projection
        d_in = h
    return total + h * m["out_dim"]


def macs_per_record(m: dict) -> int:
    h, o = m["hidden"], m["out_dim"]
    return 3 * o * h + h * (h // 2) + (h // 2)


def step_flops(m: dict, graph: dict, batch: int) -> float:
    """Every step encodes the whole graph, so the count is per step: the
    nodes' part from N and K, the head's from the batch; 2 FLOP a MAC, the
    backward twice the forward."""
    macs = (
        macs_per_node(m, graph["node_feature_dim"], graph["max_neighbors"]) * graph["num_nodes"]
        + macs_per_record(m) * batch
    )
    return 3.0 * 2.0 * macs
