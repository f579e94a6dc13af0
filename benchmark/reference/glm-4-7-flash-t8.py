"""Plain reference of the transfer-stream ranker's train step
(configuration ``glm-4-7-flash-t8``): the GLM-4.7-Flash decoder as
https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json sizes
it, an eighth of each expert layer's routed experts, the leading dense
layer and four expert layers, an eighth of the vocabulary.

float32 at ``highest`` precision, nothing of the program imported.  A
batch is ``rows`` sequences of ``positions`` records; a segment is a run
of equal child inside a row.  One row [L, D] goes through at a time:

    h   = rms(x)
    c_q = rms_q(h W_qa);  [q_nope | q_pe] = c_q W_qb                   a head
    [c_kv | k_pe] = h W_kva;  [k_nope | v] = rms_kv(c_kv) W_kvb        a head
    q_pe, k_pe = RoPE(q_pe, k_pe)      # k_pe one head, the same for all
    q = [q_nope | q_pe],  k = [k_nope | k_pe]
    a_t = sum_s softmax_s(q_t . k_s / sqrt(nope + rope)) v_s
          over s <= t in t's segment
    x'  = x + a W_o;  m = rms(x')
    layer < first_k_dense_replace:  out = x' + W_down (silu(m W_gate) * (m W_up))
    else:  s = sigmoid(m W_r);  E = the k largest of s + b
           g_e = routed_scaling_factor * s_e / sum_E s
           y = sum_{e in E, held here} g_e W_down,e (silu(m W_gate,e) * (m W_up,e))
               + W_down,s (silu(m W_gate,s) * (m W_up,s))         # no gate
           out = x' + y

and after each step, each expert layer's selection bias moves:
b_e += selection_bias_rate * sign(mean load - load_e), the loads of all
the experts over the step's rows.  Attention is a softmax over the whole
row for a block of queries under the masks; the expert layer a loop over
the experts held here, each over every token under its weight.  The
absent experts' part is left out, as in the program: the same share.

Read from the source, and assumed where it is silent (the configuration's
file lists the same): sigmoid scores (noaux_tc; no scoring_func key);
n_group 1 and topk_group 1 leave the top-k over all experts; the halves
convention for RoPE on the 64 rope dims; the norm is x / rms(x) * (1 + w),
w = 0 at the start (the weight's identity); the bias's rate 0.001
(DeepSeek-V3's) and its loads this chip's batch's.

Departures from the published model, the program's too:
  1. the input adapter ``w_in`` over [hop[src], hop[dst], previous
     target], added to the token embedding (tokens are parent host ids);
     the hop features standardised by the snapshot's columns and the
     previous target by two constants of the configuration;
  2. Huber loss on one gathered logit (the record's own parent's, from
     the history up to the previous transfer) in place of next-token
     cross-entropy;
  3. a cold-start head from features alone at a segment's first record;
  4. no auxiliary router loss, and no multi-token-prediction module;
  5. initialisation: normal(0.02) for matrices and embedding, norms at
     their identity, the bias at 0.

AdamW is followed for the dispatch's steps a leaf at a time with both
moments kept on the host between steps, so that what the device holds at
once is the parameters, the summed gradient and one row's gradient and
temporaries, beside whatever the released program's runtime has not given
back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common as C

CONTROL_BF16 = "bf16"     # a second control, for the CPU tests whose program runs in float32
F32 = jnp.float32
QUERY_BLOCK = 256


def expert_layers(m: dict) -> range:
    return range(m["first_k_dense_replace"], m["num_hidden_layers"])


# -- parameters, from the seed ----------------------------------------------------


def parameter_list(m: dict, hop_dim: int, n: int):
    """(name, kind, shape) in the order the flax module declares them: the
    order is the key each one is drawn with."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    nope, rope, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    q, kv = m["q_lora_rank"], m["kv_lora_rank"]
    e, f = m["num_experts_held"], m["moe_intermediate_size"]
    fs, wide = m["n_shared_experts"] * f, m["intermediate_size"]
    out = [
        ("w_in", "normal", (2 * hop_dim + 1, d)),
        ("final_norm", "zeros", (d,)),
        ("head", "normal", (n, d)),
        ("cold.kernel", "normal", (2 * hop_dim, 1)),
        ("cold.bias", "zeros", (1,)),
    ]
    for i in range(m["num_hidden_layers"]):
        pre = f"layer_{i}."
        out += [
            (pre + "attn.w_qa", "normal", (d, q)),
            (pre + "attn.q_norm", "zeros", (q,)),
            (pre + "attn.w_qb", "normal", (q, h * (nope + rope))),
            (pre + "attn.w_kva", "normal", (d, kv + rope)),
            (pre + "attn.kv_norm", "zeros", (kv,)),
            (pre + "attn.w_kvb", "normal", (kv, h * (nope + dv))),
            (pre + "attn.w_o", "normal", (h * dv, d)),
            (pre + "norm1", "zeros", (d,)),
            (pre + "norm2", "zeros", (d,)),
        ]
        if i not in expert_layers(m):
            out += [
                (pre + "mlp.w_gate", "normal", (d, wide)),
                (pre + "mlp.w_up", "normal", (d, wide)),
                (pre + "mlp.w_down", "normal", (wide, d)),
            ]
            continue
        out += [
            (pre + "moe.router", "normal", (d, m["n_routed_experts"])),
            (pre + "moe.w_gate", "normal", (e, d, f)),
            (pre + "moe.w_up", "normal", (e, d, f)),
            (pre + "moe.w_down", "normal", (e, f, d)),
            (pre + "moe.shared.w_gate", "normal", (d, fs)),
            (pre + "moe.shared.w_up", "normal", (d, fs)),
            (pre + "moe.shared.w_down", "normal", (fs, d)),
        ]
    return out


def _draw(kind: str, key, shape):
    if kind == "normal":
        return 0.02 * jax.random.normal(key, shape, F32)
    return jnp.zeros(shape, F32)


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
    The router, the head and the cold-start head are float32 in the
    program and stay so in a control."""
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
    """(start [L], segment id [L]) of a row."""
    start = jnp.concatenate([jnp.ones((1,), bool), dst[1:] != dst[:-1]])
    return start, jnp.cumsum(start.astype(jnp.int32))


# -- the layers, one row [L, D] at a time ---------------------------------------------------


def rotary(x, m: dict):
    """x [L, H, r]: turned by the position along the row (the halves
    convention: dim j pairs with dim j + r / 2)."""
    l, _, d = x.shape
    inv = 1.0 / (m["rope_theta"] ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = np.arange(l, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1), F32)[:, None, :]
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1), F32)[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + turned * sin


def latent_attention(p, x, seg, m: dict, variant: str):
    """Every head's keys and values made from the latents (decompressed)."""
    l = x.shape[0]
    h, nope, rope, dv = m["num_attention_heads"], m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    eps = m["rms_norm_eps"]
    q = dense(rms(dense(x, p["w_qa"], variant), p["q_norm"], eps), p["w_qb"], variant).reshape(l, h, nope + rope)
    a = dense(x, p["w_kva"], variant)
    c_kv, k_pe = a[:, : m["kv_lora_rank"]], a[:, m["kv_lora_rank"]:]
    kv = dense(rms(c_kv, p["kv_norm"], eps), p["w_kvb"], variant).reshape(l, h, nope + dv)
    k_pe = jnp.broadcast_to(rotary(k_pe[:, None, :], m), (l, h, rope))
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], m)], -1)
    k = jnp.concatenate([kv[..., :nope], k_pe], -1)
    v = kv[..., nope:]
    idx = jnp.arange(l)
    block = min(QUERY_BLOCK, l)

    @jax.checkpoint
    def rows_of_queries(at):
        """A block of queries against every key of the row; its [H, block,
        L] weights are made again in the backward, not kept."""
        q_b, seg_b, idx_b = at
        s = jnp.einsum("qhd,shd->hqs", q_b, k, precision="highest") * (nope + rope) ** -0.5
        ok = (seg_b[:, None] == seg[None, :]) & (idx_b[:, None] >= idx[None, :])
        w = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shd->qhd", w, v, precision="highest")

    fold = lambda t: t.reshape(l // block, block, *t.shape[1:])
    o = jax.lax.map(rows_of_queries, (fold(q), fold(seg), fold(idx))).reshape(l, h * dv)
    return dense(o, p["w_o"], variant)


def swiglu(p, x, variant: str):
    return dense(silu(dense(x, p["w_gate"], variant)) * dense(x, p["w_up"], variant), p["w_down"], variant)


def expert_layer(p, x, bias, m: dict, variant: str):
    """x [L, D] -> (y [L, D], slots routed to each of all the experts)."""
    k = m["num_experts_per_tok"]
    first, count = m["experts_held_first"], m["num_experts_held"]
    s = jax.nn.sigmoid(C._dot(x, p["router"]))
    kth = jax.lax.top_k(s + bias, k)[0][:, -1:]
    chosen = (s + bias) >= kth
    g = jnp.where(chosen, s, 0.0)
    if m["norm_topk_prob"]:
        g = g / g.sum(-1, keepdims=True)
    g = m["routed_scaling_factor"] * g
    held = jax.lax.dynamic_slice_in_dim(g, first, count, axis=1)       # [L, count]

    @jax.checkpoint
    def one(w_e, gate, up, down):
        """One held expert over every token; made again in the backward."""
        return w_e[:, None] * swiglu({"w_gate": gate, "w_up": up, "w_down": down}, x, variant)

    y, _ = jax.lax.scan(
        lambda y, xs: (y + one(*xs), None), jnp.zeros_like(x),
        (held.T, p["w_gate"], p["w_up"], p["w_down"]),
    )
    return y + swiglu(p["shared"], x, variant), chosen.sum(0).astype(jnp.int32)


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


def block(lp, x, seg, bias, m: dict, layer: int, variant: str):
    """(out, slots routed to each expert, zeros for the dense layer)."""
    eps = m["rms_norm_eps"]
    x = x + latent_attention(lp["attn"], rms(x, lp["norm1"], eps), seg, m, variant)
    h = rms(x, lp["norm2"], eps)
    if layer not in expert_layers(m):
        return x + swiglu(lp["mlp"], h, variant), jnp.zeros((m["n_routed_experts"],), jnp.int32)
    y, loads = expert_layer(lp["moe"], h, bias, m, variant)
    return x + y, loads


def row_predictions(flat_params, table, src, dst, prev_y, biases, m: dict, variant: str):
    """One row of L records -> (the L predictions, the slots routed to
    each expert by expert layer [expert layers, experts]).  ``table`` is
    ``standard_table``'s, ``prev_y`` a row of ``previous_targets``',
    ``biases`` [expert layers, experts] the selection biases."""
    p = nest(flat_params)
    start, seg = segment_of(dst)
    feats = jnp.concatenate([table[src], table[dst]], -1)
    x = p["embed"]["embedding"][src] + dense(
        jnp.concatenate([feats, prev_y[:, None]], -1), p["w_in"], variant
    )
    loads = []
    for i in range(m["num_hidden_layers"]):
        bias = biases[i - m["first_k_dense_replace"]] if i in expert_layers(m) else None
        x, n = jax.checkpoint(functools.partial(block, m=m, layer=i, variant=variant))(
            p[f"layer_{i}"], x, seg, bias
        )
        if i in expert_layers(m):
            loads.append(n)
    h = rms(x, p["final_norm"], m["rms_norm_eps"])
    warm = jnp.sum(delayed(h, 1) * p["head"][src], axis=-1)
    cold = C._dot(feats, p["cold"]["kernel"])[:, 0] + p["cold"]["bias"][0]
    return jnp.where(start, cold, warm), jnp.stack(loads)


def previous_targets(dst, y, m: dict):
    """[rows, L]: the target of the record before in the same segment,
    less the configuration's ``target_center`` and over its
    ``target_scale``; 0 at a segment's first record."""
    start = np.concatenate([np.ones((dst.shape[0], 1), bool), dst[:, 1:] != dst[:, :-1]], 1)
    prev = np.concatenate([np.zeros((y.shape[0], 1), y.dtype), y[:, :-1]], 1)
    prev = (prev.astype(np.float32) - np.float32(m["target_center"])) / np.float32(m["target_scale"])
    return np.where(start, np.float32(0.0), prev)


def next_biases(biases, loads, records: int, m: dict):
    """The loss-free balancing rule after a step: each expert's bias up by
    the rate where its load is under the mean, down where over."""
    mean = m["num_experts_per_tok"] * records / m["n_routed_experts"]
    return biases + m["selection_bias_rate"] * np.sign(mean - loads.astype(np.float64)).astype(np.float32)


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
    [steps, batch]), init_key.  Also returns the selection biases after
    each step (``biases``, [steps, expert layers, experts])."""
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
    biases = np.zeros((len(expert_layers(model)), model["n_routed_experts"]), np.float32)

    @jax.jit
    def row(p, a, b, t, prev, biases):
        def loss(p):
            pred, loads = row_predictions(p, hop, a, b, prev, biases, model, variant)
            return C.huber_sum(pred, t) / used, loads
        return jax.value_and_grad(loss, has_aux=True)(p)

    mu = {k: np.zeros(v.shape, np.float32) for k, v in params.items()}
    nu = {k: np.zeros(v.shape, np.float32) for k, v in params.items()}
    losses, mean_residual, first_grad_norm, after = [], [], None, []
    for t in range(steps):
        shape = (rows, l)
        s_t, d_t, y_t = src[t].reshape(shape), dst[t].reshape(shape), y[t].reshape(shape)
        prev = previous_targets(d_t, y_t, model)
        total, grads, loads = 0.0, None, 0
        for r in range(used_rows):
            (part, n_r), g = row(params, *(jnp.asarray(a[r]) for a in (s_t, d_t, y_t, prev)), jnp.asarray(biases))
            total += float(part)
            loads = loads + np.asarray(n_r)
            grads = g if grads is None else {k: _add_into(grads[k], g[k]) for k in g}
            del g
        biases = next_biases(biases, loads, used, model)
        after.append(biases)
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
        "biases": np.stack(after),
    }
