"""Plain reference of the hop ranker's train step (configuration
``hop-h1024``): SIGN-style precomputed hop features, a shared two-layer
GELU encoder over each endpoint's row and learned node embedding, an edge
head over [s, d, s*d], Huber loss, AdamW.

float32 at ``highest`` precision throughout; the batch goes through in
blocks of rows whose gradients are summed, so the full-size step fits
beside nothing else on the chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common as C

ENC = "HopEncoder_0"
BLOCK_ROWS = 32_768


def init_params(seed_key, num_nodes: int, feat_dim: int, m: dict):
    hidden, out, embed = m["hidden"], m["out_dim"], m["node_embed_dim"]
    embedding = jax.nn.initializers.variance_scaling(
        1.0, "fan_in", "normal", out_axis=0
    )(C.flax_key(seed_key, ENC, "Embed_0", 1), (num_nodes, embed), jnp.float32)
    return {
        ENC: {
            "Embed_0": {"embedding": embedding},
            "Dense_0": C.dense_init(seed_key, (ENC, "Dense_0"), feat_dim + embed, hidden),
            "Dense_1": C.dense_init(seed_key, (ENC, "Dense_1"), hidden, hidden),
            "Dense_2": C.dense_init(seed_key, (ENC, "Dense_2"), hidden, out),
        },
        "Dense_0": C.dense_init(seed_key, ("Dense_0",), 3 * out, hidden),
        "Dense_1": C.dense_init(seed_key, ("Dense_1",), hidden, hidden // 2),
        "Dense_2": C.dense_init(seed_key, ("Dense_2",), hidden // 2, 1),
    }


def _encode(p, rows, ids, mask, rate, variant):
    x = jnp.concatenate([rows, p["Embed_0"]["embedding"][ids]], -1)
    x = C.gelu(C.dense(p["Dense_0"], x, variant))
    x = C.dropout(x, mask, rate)
    x = C.gelu(C.dense(p["Dense_1"], x, variant))
    return C.dense(p["Dense_2"], x, variant)


def predict(params, hop, src, dst, mask_s, mask_d, rate, variant):
    s = _encode(params[ENC], hop[src], src, mask_s, rate, variant)
    d = _encode(params[ENC], hop[dst], dst, mask_d, rate, variant)
    x = jnp.concatenate([s, d, s * d], -1)
    x = C.gelu(C.dense(params["Dense_0"], x, variant))
    x = C.gelu(C.dense(params["Dense_1"], x, variant))
    return C.dense(params["Dense_2"], x, variant)[..., 0]


def first_steps(model: dict, train: dict, inputs: dict, variant: str = C.KEEP_F32):
    """Follow the first ``len(batches)`` steps.  ``inputs``: node_feats,
    topo (src, dst, rtt), max_neighbors, batches (src, dst, y each
    [steps, batch]), init_key, dropout_key, bias_shift."""
    n = inputs["node_feats"].shape[0]
    idx, msk, ef = C.neighbor_table(n, *inputs["topo"], inputs["max_neighbors"])
    hop = jnp.asarray(C.hop_features(inputs["node_feats"], idx, msk, ef, model["hops"]))
    params = init_params(inputs["init_key"], n, hop.shape[1], model)
    params["Dense_2"]["bias"] = params["Dense_2"]["bias"] + inputs["bias_shift"]
    src, dst, y = inputs["batches"]
    steps, batch = src.shape
    rate, hidden = model["dropout"], model["hidden"]
    used = batch // 2 if variant == C.FAULT_HALF else batch

    @jax.jit
    def block(p, a, b, t, ms, md):
        def loss(p):
            return C.huber_sum(predict(p, hop, a, b, ms, md, rate, variant), t) / used
        return jax.value_and_grad(loss)(p)

    masks = jax.jit(
        lambda key, call: C.keep_mask(key, (ENC, "Dropout_0", call), (batch, hidden), rate),
        static_argnums=1,
    )

    def grad_fn(p, t):
        key = jax.random.fold_in(inputs["dropout_key"], t)
        ms, md = masks(key, 1), masks(key, 2)
        total, grads = 0.0, None
        for lo in range(0, used, BLOCK_ROWS):
            hi = min(lo + BLOCK_ROWS, used)
            l, g = block(
                p, jnp.asarray(src[t, lo:hi]), jnp.asarray(dst[t, lo:hi]),
                jnp.asarray(y[t, lo:hi]), ms[lo:hi], md[lo:hi],
            )
            total = total + l
            grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
        return total, grads

    return C.follow(params, grad_fn, steps, train)
