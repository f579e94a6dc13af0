"""``hop-h1024``: how the program is told this configuration, and the
operations one train step needs, from the shapes."""

from __future__ import annotations


def model_config(m: dict):
    import jax.numpy as jnp

    from dragonfly2_tpu.models.hop import HopConfig

    return HopConfig(
        hidden=m["hidden"], out_dim=m["out_dim"], hops=m["hops"],
        node_embed_dim=m["node_embed_dim"], dropout=m["dropout"],
        dtype=jnp.dtype(m["dtype"]),
    )


def batch_job(m: dict, node_feats, table):
    """(entry point, its node-feature argument, further keywords) of the
    job the trainer service runs at end of stream.  The hop features are
    computed once per snapshot, before the job."""
    import jax.numpy as jnp
    import numpy as np

    from dragonfly2_tpu.models.hop import precompute_hop_features_jit
    from dragonfly2_tpu.trainer.train import train_hop_ranker

    hop = np.asarray(
        precompute_hop_features_jit(jnp.asarray(node_feats, jnp.float32), table, hops=m["hops"])
    )
    return train_hop_ranker, node_feats, {"hop_feats": hop}


def macs_per_record(m: dict, feat_dim: int) -> int:
    """Multiply-accumulates of one record's forward pass."""
    hop_dim = feat_dim * (1 + 2 * m["hops"]) + 2
    h, o = m["hidden"], m["out_dim"]
    encoder = (hop_dim + m["node_embed_dim"]) * h + h * h + h * o
    head = 3 * o * h + h * (h // 2) + (h // 2)
    return 2 * encoder + head


def step_flops(m: dict, graph: dict, batch: int) -> float:
    """Forward and backward of one step: 2 FLOP a MAC, the backward twice
    the forward.  The gathers, the embedding scatter and the optimizer are
    not matrix work and are left out."""
    return 3.0 * 2.0 * macs_per_record(m, graph["node_feature_dim"]) * batch
