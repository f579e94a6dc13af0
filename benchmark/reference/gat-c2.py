"""Plain reference of the GAT ranker's train step (configuration
``gat-c2``): learned node embedding beside the host features, two
multi-head attention layers over each node's K neighbour slots (keys and
values projected after the gather, an edge-feature bias on the logit,
masked softmax, a residual inside the GELU), dropout after each layer, a
linear read-out, the same edge head and loss as the hop ranker.

float32 at ``highest`` precision.  Every step encodes the whole graph, so
each layer runs over blocks of nodes under ``jax.checkpoint``: the
backward pass rebuilds one block's [rows, K, D] tensors at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common as C

BLOCK_NODES = 16_384


def init_params(seed_key, num_nodes: int, feat_dim: int, m: dict):
    hidden, embed = m["hidden"], m["node_embed_dim"]
    params = {
        "NodeEmbedding_0": {
            "embedding": 0.1 * jax.random.normal(
                C.flax_key(seed_key, "NodeEmbedding_0", 1), (num_nodes, embed), jnp.float32
            )
        }
    }
    d_in = feat_dim + embed
    for i in range(m["num_layers"]):
        name = f"GATLayer_{i}"
        params[name] = {
            "Dense_0": C.dense_init(seed_key, (name, "Dense_0"), d_in, hidden),   # queries
            "Dense_1": C.dense_init(seed_key, (name, "Dense_1"), d_in, hidden),   # keys
            "Dense_2": C.dense_init(seed_key, (name, "Dense_2"), d_in, hidden),   # values
            "Dense_3": C.dense_init(seed_key, (name, "Dense_3"), m["edge_dim"], m["num_heads"]),
            "Dense_4": C.dense_init(seed_key, (name, "Dense_4"), hidden, hidden),
        }
        d_in = hidden
    params["Dense_0"] = C.dense_init(seed_key, ("Dense_0",), hidden, m["out_dim"])
    params["Dense_1"] = C.dense_init(seed_key, ("Dense_1",), 3 * m["out_dim"], hidden)
    params["Dense_2"] = C.dense_init(seed_key, ("Dense_2",), hidden, hidden // 2)
    params["Dense_3"] = C.dense_init(seed_key, ("Dense_3",), hidden // 2, 1)
    return params


def _layer(p, h, table, heads: int, variant: str, block: int):
    idx, msk, ef = table
    n, k = idx.shape
    width = p["Dense_0"]["kernel"].shape[1] // heads
    q = C.dense(p["Dense_0"], h, variant).reshape(n, heads, width)

    @jax.checkpoint
    def rows(args):
        q_b, idx_b, msk_b, ef_b = args
        h_n = h[idx_b]                                             # [b, K, D]
        keys = C.dense(p["Dense_1"], h_n, variant).reshape(-1, k, heads, width)
        vals = C.dense(p["Dense_2"], h_n, variant).reshape(-1, k, heads, width)
        logits = jnp.einsum(
            "nhw,nkhw->nkh", q_b, keys, precision=jax.lax.Precision.HIGHEST
        ) / jnp.sqrt(jnp.float32(width)) + C.dense(p["Dense_3"], ef_b, variant)
        logits = jnp.where(msk_b[..., None] > 0, logits, jnp.finfo(jnp.float32).min)
        attn = jax.nn.softmax(logits, axis=1) * msk_b[..., None]
        out = jnp.einsum(
            "nkh,nkhw->nhw", attn, vals, precision=jax.lax.Precision.HIGHEST
        ).reshape(-1, heads * width)
        return C.gelu(C.dense(p["Dense_4"], out, variant) + out)

    block = min(block, n)
    if n % block:
        raise ValueError(f"{n} nodes do not divide into blocks of {block}")
    split = lambda a: a.reshape((n // block, block) + a.shape[1:])
    out = jax.lax.map(rows, (split(q), split(idx), split(msk), split(ef)))
    return out.reshape(n, -1)


def predict(params, node_feats, table, src, dst, masks, m, variant, block=BLOCK_NODES):
    h = jnp.concatenate([node_feats, params["NodeEmbedding_0"]["embedding"]], -1)
    for i in range(m["num_layers"]):
        h = _layer(params[f"GATLayer_{i}"], h, table, m["num_heads"], variant, block)
        h = C.dropout(h, masks[i], m["dropout"])
    emb = C.dense(params["Dense_0"], h, variant)
    s, d = emb[src], emb[dst]
    x = jnp.concatenate([s, d, s * d], -1)
    x = C.gelu(C.dense(params["Dense_1"], x, variant))
    x = C.gelu(C.dense(params["Dense_2"], x, variant))
    return C.dense(params["Dense_3"], x, variant)[..., 0]


def first_steps(model: dict, train: dict, inputs: dict, variant: str = C.KEEP_F32):
    """Same contract as the hop reference's ``first_steps``."""
    x = jnp.asarray(inputs["node_feats"], jnp.float32)
    n = x.shape[0]
    table = tuple(
        jnp.asarray(a) for a in C.neighbor_table(n, *inputs["topo"], inputs["max_neighbors"])
    )
    params = init_params(inputs["init_key"], n, x.shape[1], model)
    params["Dense_3"]["bias"] = params["Dense_3"]["bias"] + inputs["bias_shift"]
    src, dst, y = inputs["batches"]
    steps, batch = src.shape
    used = batch // 2 if variant == C.FAULT_HALF else batch

    @jax.jit
    def grads(p, key, a, b, t):
        masks = [
            C.keep_mask(key, (f"Dropout_{i}", 1), (n, model["hidden"]), model["dropout"])
            for i in range(model["num_layers"])
        ]
        def loss(p):
            return C.huber_sum(predict(p, x, table, a, b, masks, model, variant), t) / used
        return jax.value_and_grad(loss)(p)

    def grad_fn(p, t):
        key = jax.random.fold_in(inputs["dropout_key"], t)
        return grads(
            p, key, jnp.asarray(src[t, :used]), jnp.asarray(dst[t, :used]),
            jnp.asarray(y[t, :used]),
        )

    return C.follow(params, grad_fn, steps, train)
