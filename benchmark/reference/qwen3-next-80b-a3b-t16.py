"""Plain reference of the transfer-stream ranker's train step
(configuration ``qwen3-next-80b-a3b-t16``): the Qwen3-Next decoder as
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json
sizes it, one sixteenth of each layer's experts, four layers, an eighth
of the vocabulary.

float32 at ``highest`` precision, nothing of the program imported.  A
batch is ``rows`` sequences of ``positions`` records; a segment is a run
of equal child inside a row.  One row goes through at a time:

- Gated DeltaNet: the recurrence token by token in ``lax.scan``
  (S <- exp(g) S, nought at a segment's start; u = beta (v - S^T k);
  S <- S + k u^T; o = S^T q), checkpointed every 64 tokens so that its
  backward keeps no per-token state;
- gated attention: softmax over the whole row for a block of queries,
  masked to the causal part of the query's segment;
- the expert layer: softmax over all 512 experts, the ten largest
  renormalised, then a loop over the experts held here, each over every
  token under its mask.  The absent experts' part is left out, as in the
  program: the same share.

Departures from the published model, the program's too:
  1. the input adapter ``w_in`` over [hop[src], hop[dst], previous
     target], added to the token embedding (tokens are parent host ids);
     the hop features standardised by the snapshot's columns and the
     previous target by two constants of the configuration
     (``target_center``, ``target_scale``), so that records do not all
     look alike to the first norm;
  2. Huber loss on one gathered logit (the record's own parent's, from
     the history up to the previous transfer) in place of next-token
     cross-entropy;
  3. a cold-start head from features alone at a segment's first record;
  4. no auxiliary router loss and no multi-token-prediction module;
  5. initialisation: normal(0.02) for matrices, conv taps and embedding,
     A_log = log U(1e-4, 16), dt_bias = 1, norms at their identity.

AdamW is followed for the dispatch's steps a leaf at a time with both
moments kept on the host between steps, so that what the device holds at
once is the parameters, the summed gradient and one row's gradient and
temporaries (7.5 GB and a row at the cell's size), beside whatever the
released program's runtime has not given back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common as C

CONTROL_BF16 = "bf16"     # a second control, for the CPU tests whose program runs in float32
F32 = jnp.float32
SCAN_CHECKPOINT = 64
QUERY_BLOCK = 512
EPS_L2 = 1e-6


# -- parameters, from the seed ----------------------------------------------------


def is_attention(i: int, m: dict) -> bool:
    return (i + 1) % m["full_attention_interval"] == 0


def parameter_list(m: dict, hop_dim: int, n: int):
    """(name, kind, shape) in the order the flax module declares them: the
    order is the key each one is drawn with."""
    d = m["hidden_size"]
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    h, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    e, f, fs = m["num_experts_held"], m["moe_intermediate_size"], m["shared_expert_intermediate_size"]
    out = [
        ("w_in", "normal", (2 * hop_dim + 1, d)),
        ("final_norm", "zeros", (d,)),
        ("head", "normal", (n, d)),
        ("cold.kernel", "normal", (2 * hop_dim, 1)),
        ("cold.bias", "zeros", (1,)),
    ]
    for i in range(m["num_hidden_layers"]):
        pre = f"layer_{i}."
        if is_attention(i, m):
            out += [
                (pre + "attn.w_q", "normal", (d, 2 * h * hd)),
                (pre + "attn.w_k", "normal", (d, kv * hd)),
                (pre + "attn.w_v", "normal", (d, kv * hd)),
                (pre + "attn.q_norm", "zeros", (hd,)),
                (pre + "attn.k_norm", "zeros", (hd,)),
                (pre + "attn.w_o", "normal", (h * hd, d)),
            ]
        else:
            out += [
                (pre + "gdn.w_qkvz", "normal", (d, 2 * hk * dk + 2 * hv * dv)),
                (pre + "gdn.w_ba", "normal", (d, 2 * hv)),
                (pre + "gdn.conv", "normal", (m["linear_conv_kernel_dim"], 2 * hk * dk + hv * dv)),
                (pre + "gdn.A_log", "a_log", (hv,)),
                (pre + "gdn.dt_bias", "ones", (hv,)),
                (pre + "gdn.norm", "ones", (dv,)),
                (pre + "gdn.w_o", "normal", (hv * dv, d)),
            ]
        out += [
            (pre + "norm1", "zeros", (d,)),
            (pre + "norm2", "zeros", (d,)),
            (pre + "moe.router", "normal", (d, m["num_experts"])),
            (pre + "moe.w_gate", "normal", (e, d, f)),
            (pre + "moe.w_up", "normal", (e, d, f)),
            (pre + "moe.w_down", "normal", (e, f, d)),
            (pre + "moe.shared.w_gate", "normal", (d, fs)),
            (pre + "moe.shared.w_up", "normal", (d, fs)),
            (pre + "moe.shared.w_down", "normal", (fs, d)),
            (pre + "moe.shared_gate", "normal", (d, 1)),
        ]
    return out


def _draw(kind: str, key, shape):
    if kind == "normal":
        return 0.02 * jax.random.normal(key, shape, F32)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, F32, 1e-4, 16.0))
    return (jnp.ones if kind == "ones" else jnp.zeros)(shape, F32)


def init_params(seed_key, m: dict, hop_dim: int, n: int) -> dict:
    """{flattened name: array}, equal to the flax module's bit for bit: the
    module's own parameters take the keys 1, 2, ... of its scope in the
    order declared, the embedding the first key of its own."""
    params = {
        "embed/embedding": _draw("normal", C.flax_key(seed_key, "embed", 1), (n, m["hidden_size"]))
    }
    for count, (name, kind, shape) in enumerate(parameter_list(m, hop_dim, n), start=1):
        params[name] = _draw(kind, C.flax_key(seed_key, count), shape)
    return params


# -- arithmetic ------------------------------------------------------------------------


@jax.custom_vjp
def _bf16_dot(x, w):
    """A matmul as a bfloat16 path runs it: operands and the incoming
    gradient rounded to bfloat16, float32 accumulation."""
    r = lambda a: a.astype(jnp.bfloat16).astype(F32)
    return C._dot(r(x), r(w))


def _bf16_dot_fwd(x, w):
    r = lambda a: a.astype(jnp.bfloat16).astype(F32)
    return C._dot(r(x), r(w)), (r(x), r(w))


def _bf16_dot_bwd(res, g):
    xq, wq = res
    gq = g.astype(jnp.bfloat16).astype(F32)
    lead = xq.reshape(-1, xq.shape[-1])
    return C._dot(gq, wq.T), C._dot(lead.T, gq.reshape(-1, gq.shape[-1]))


_bf16_dot.defvjp(_bf16_dot_fwd, _bf16_dot_bwd)


def dense(x, w, variant: str):
    """Every matmul the program runs on the MXU in its activations' type.
    The router, the decay's and the gates' projections, the head and the
    cold-start head are float32 in the program and stay so in a control."""
    if variant == C.CONTROL_FP8:
        return C._fp8_dot(x, w)
    if variant == CONTROL_BF16:
        return _bf16_dot(x, w)
    return C._dot(x, w)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def silu(x):
    return x * jax.nn.sigmoid(x)


def delayed(x, by: int):
    """x [L, ...] delayed by ``by`` tokens, zeros in."""
    if by == 0:
        return x
    return jnp.concatenate([jnp.zeros((by, *x.shape[1:]), x.dtype), x[:-by]], 0)


def segment_of(dst):
    """(start [L], segment id [L], position in the segment [L]) of a row."""
    l = dst.shape[0]
    start = jnp.concatenate([jnp.ones((1,), bool), dst[1:] != dst[:-1]])
    seg = jnp.cumsum(start.astype(jnp.int32))
    idx = jnp.arange(l)
    first = jax.lax.cummax(jnp.where(start, idx, 0), axis=0)
    return start, seg, idx - first


# -- the layers, one row [L, D] at a time ---------------------------------------------------


def gated_delta_net(p, x, start, pos, m: dict, variant: str):
    l = x.shape[0]
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    qkvz = dense(x, p["w_qkvz"], variant)
    ba = C._dot(x, p["w_ba"])
    cut = 2 * hk * dk + hv * dv
    qkv, z = qkvz[:, :cut], qkvz[:, cut:]
    b, a = ba[:, :hv], ba[:, hv:]
    conv = sum(
        jnp.where((pos >= j)[:, None], delayed(qkv, j), 0.0) * p["conv"][j]
        for j in range(m["linear_conv_kernel_dim"])
    )
    qkv = silu(conv)
    q = qkv[:, : hk * dk].reshape(l, hk, dk)
    k = qkv[:, hk * dk: 2 * hk * dk].reshape(l, hk, dk)
    v = qkv[:, 2 * hk * dk:].reshape(l, hv, dv)
    unit = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + EPS_L2)
    # Each key head serves hv / hk value heads, neighbours.
    q = jnp.repeat(unit(q) * dk ** -0.5, hv // hk, axis=1)
    k = jnp.repeat(unit(k), hv // hk, axis=1)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])

    def token(s, xs):
        q_t, k_t, v_t, g_t, b_t, first = xs
        s = jnp.where(first, 0.0, jnp.exp(g_t))[:, None, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t, precision="highest"))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision="highest")

    @jax.checkpoint
    def stretch(s, xs):
        return jax.lax.scan(token, s, xs)

    every = min(SCAN_CHECKPOINT, l)
    fold = lambda t: t.reshape(l // every, every, *t.shape[1:])
    _, o = jax.lax.scan(
        stretch, jnp.zeros((hv, dk, dv), F32),
        tuple(fold(t) for t in (q, k, v, g, beta, start)),
    )
    o = o.reshape(l, hv, dv)
    o = p["norm"] * o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + m["rms_norm_eps"])
    o = o.reshape(l, hv * dv) * silu(z)
    return dense(o, p["w_o"], variant)


def rotary(x, m: dict):
    """x [L, H, d]: the first ``partial_rotary_factor`` of the dims turned
    by the position along the row (the halves convention)."""
    l, _, d = x.shape
    n = int(d * m["partial_rotary_factor"])
    inv = 1.0 / (m["rope_theta"] ** (np.arange(0, n, 2, dtype=np.float64) / n))
    ang = np.arange(l, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1), F32)[:, None, :]
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1), F32)[:, None, :]
    rot = x[..., :n]
    turned = jnp.concatenate([-rot[..., n // 2:], rot[..., : n // 2]], -1)
    return jnp.concatenate([rot * cos + turned * sin, x[..., n:]], -1)


def gated_attention(p, x, seg, m: dict, variant: str):
    l = x.shape[0]
    h, kv, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    qg = dense(x, p["w_q"], variant).reshape(l, h, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = dense(x, p["w_k"], variant).reshape(l, kv, d)
    v = dense(x, p["w_v"], variant).reshape(l, kv, d)
    q = rotary(rms(q, p["q_norm"], m["rms_norm_eps"]), m)
    k = rotary(rms(k, p["k_norm"], m["rms_norm_eps"]), m)
    k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
    idx = jnp.arange(l)
    block = min(QUERY_BLOCK, l)

    @jax.checkpoint
    def rows_of_queries(at):
        """A block of queries against every key of the row; its [H, block,
        L] weights are made again in the backward, not kept."""
        q_b, seg_b, idx_b = at
        s = jnp.einsum("qhd,shd->hqs", q_b, k, precision="highest") * d ** -0.5
        ok = (seg_b[:, None] == seg[None, :]) & (idx_b[:, None] >= idx[None, :])
        w = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shd->qhd", w, v, precision="highest")

    fold = lambda t: t.reshape(l // block, block, *t.shape[1:])
    o = jax.lax.map(rows_of_queries, (fold(q), fold(seg), fold(idx))).reshape(l, h, d)
    o = o * jax.nn.sigmoid(gate)
    return dense(o.reshape(l, h * d), p["w_o"], variant)


def expert_layer(p, x, m: dict, variant: str):
    k = m["num_experts_per_tok"]
    first, count = m["experts_held_first"], m["num_experts_held"]
    probs = jax.nn.softmax(C._dot(x, p["router"]), axis=-1)
    tenth = jax.lax.top_k(probs, k)[0][:, -1:]
    chosen = jnp.where(probs >= tenth, probs, 0.0)
    if m["norm_topk_prob"]:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    held = jax.lax.dynamic_slice_in_dim(chosen, first, count, axis=1)       # [L, count]

    def one(y, xs):
        w_e, gate, up, down = xs
        h = silu(dense(x, gate, variant)) * dense(x, up, variant)
        return y + w_e[:, None] * dense(h, down, variant), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(x), (held.T, p["w_gate"], p["w_up"], p["w_down"])
    )
    s = p["shared"]
    shared = dense(silu(dense(x, s["w_gate"], variant)) * dense(x, s["w_up"], variant), s["w_down"], variant)
    return y + jax.nn.sigmoid(C._dot(x, p["shared_gate"])) * shared


def nest(flat: dict) -> dict:
    out: dict = {}
    for name, value in flat.items():
        *path, leaf = name.replace("/", ".").split(".")
        node = out
        for part in path:
            node = node.setdefault(part, {})
        node[leaf] = value
    return out


def standard_table(hop):
    """The snapshot's hop features, each column less its mean over its
    standard deviation (+ 1e-3) over the hosts."""
    hop = jnp.asarray(hop, F32)
    return (hop - hop.mean(0)) / (hop.std(0) + 1e-3)


def row_predictions(flat_params, table, src, dst, prev_y, m: dict, variant: str):
    """One row of L records -> the L predictions.  ``table`` is
    ``standard_table``'s, ``prev_y`` a row of ``previous_targets``'."""
    p = nest(flat_params)
    start, seg, pos = segment_of(dst)
    feats = jnp.concatenate([table[src], table[dst]], -1)
    x = p["embed"]["embedding"][src] + dense(
        jnp.concatenate([feats, prev_y[:, None]], -1), p["w_in"], variant
    )
    for i in range(m["num_hidden_layers"]):
        lp = p[f"layer_{i}"]

        @jax.checkpoint
        def layer(lp, x, attention=is_attention(i, m)):
            h = rms(x, lp["norm1"], m["rms_norm_eps"])
            if attention:
                x = x + gated_attention(lp["attn"], h, seg, m, variant)
            else:
                x = x + gated_delta_net(lp["gdn"], h, start, pos, m, variant)
            return x + expert_layer(lp["moe"], rms(x, lp["norm2"], m["rms_norm_eps"]), m, variant)

        x = layer(lp, x)
    h = rms(x, p["final_norm"], m["rms_norm_eps"])
    warm = jnp.sum(delayed(h, 1) * p["head"][src], axis=-1)
    cold = C._dot(feats, p["cold"]["kernel"])[:, 0] + p["cold"]["bias"][0]
    return jnp.where(start, cold, warm)


def previous_targets(dst, y, m: dict):
    """[rows, L]: the target of the record before in the same segment,
    less the configuration's ``target_center`` and over its
    ``target_scale``; 0 at a segment's first record."""
    start = np.concatenate([np.ones((dst.shape[0], 1), bool), dst[:, 1:] != dst[:, :-1]], 1)
    prev = np.concatenate([np.zeros((y.shape[0], 1), y.dtype), y[:, :-1]], 1)
    prev = (prev.astype(np.float32) - np.float32(m["target_center"])) / np.float32(m["target_scale"])
    return np.where(start, np.float32(0.0), prev)


# -- AdamW, the state updated in place ----------------------------------------------------


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adamw_leaf(p, mu, nu, g, clip, t, lr, wd):
    b1, b2, eps = 0.9, 0.999, 1e-8
    g = g * clip
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * g * g
    c1, c2 = 1 - b1 ** (t + 1), 1 - b2 ** (t + 1)
    return p - lr * ((mu / c1) / (jnp.sqrt(nu / c2) + eps) + wd * p), mu, nu


_sum_squares = jax.jit(lambda g: jnp.sum(g * g))
_add_into = jax.jit(jnp.add, donate_argnums=(0,))
_norm = lambda a: float(jnp.linalg.norm(a))


def first_steps(model: dict, train: dict, inputs: dict, variant: str = C.KEEP_F32):
    """Follow the first ``len(batches)`` steps.  ``inputs``: node_feats,
    topo (src, dst, rtt), max_neighbors, batches (src, dst, y each
    [steps, batch]), init_key."""
    n = inputs["node_feats"].shape[0]
    idx, msk, ef = C.neighbor_table(n, *inputs["topo"], inputs["max_neighbors"])
    hop = standard_table(C.hop_features(inputs["node_feats"], idx, msk, ef, model["hops"]))
    params = init_params(inputs["init_key"], model, hop.shape[1], n)
    start_params = {k: np.asarray(v) for k, v in params.items()}
    src, dst, y = (np.asarray(a) for a in inputs["batches"])
    steps, batch = src.shape
    l = min(model["positions"], batch)
    rows = batch // l
    used_rows = rows // 2 if variant == C.FAULT_HALF else rows
    used = used_rows * l

    @jax.jit
    def row(p, a, b, t, prev):
        def loss(p):
            return C.huber_sum(row_predictions(p, hop, a, b, prev, model, variant), t) / used
        return jax.value_and_grad(loss)(p)

    mu = {k: np.zeros(v.shape, np.float32) for k, v in params.items()}
    nu = {k: np.zeros(v.shape, np.float32) for k, v in params.items()}
    losses, mean_residual, first_grad_norm = [], [], None
    for t in range(steps):
        shape = (rows, l)
        s_t, d_t, y_t = src[t].reshape(shape), dst[t].reshape(shape), y[t].reshape(shape)
        prev = previous_targets(d_t, y_t, model)
        total, grads = 0.0, None
        for r in range(used_rows):
            part, g = row(params, *(jnp.asarray(a[r]) for a in (s_t, d_t, y_t, prev)))
            total += float(part)
            grads = g if grads is None else {k: _add_into(grads[k], g[k]) for k in g}
            del g
        if first_grad_norm is None:
            first_grad_norm = {k: _norm(v) for k, v in grads.items()}
        mean_residual.append(sum(abs(float(v.reshape(()))) for v in grads.values() if v.size == 1))
        if t >= train["warmup_steps"]:
            raise ValueError("the reference follows the warm-up only")
        lr = train["learning_rate"] * t / train["warmup_steps"]
        gnorm = float(np.sqrt(sum(float(_sum_squares(v)) for v in grads.values())))
        clip = 1.0 if gnorm < 1.0 else 1.0 / gnorm
        for k in list(params):
            params[k], m_k, v_k = _adamw_leaf(
                params[k], jnp.asarray(mu[k]), jnp.asarray(nu[k]), grads.pop(k), F32(clip),
                F32(t), F32(lr), F32(train["weight_decay"]),
            )
            mu[k], nu[k] = np.asarray(m_k), np.asarray(v_k)
        losses.append(total)
    change = {
        k: float(np.linalg.norm(np.asarray(v, np.float64) - start_params[k]))
        for k, v in params.items()
    }
    return {
        "init_params": start_params,
        "losses": losses,
        "mean_residual": sum(mean_residual) / len(mean_residual),
        "moment": mu,
        "moment_norm": {k: float(np.linalg.norm(v.astype(np.float64))) for k, v in mu.items()},
        "second_moment_norm": {k: float(np.linalg.norm(v.astype(np.float64))) for k, v in nu.items()},
        "change_norm": change,
        "first_grad_norm": first_grad_norm,
    }
