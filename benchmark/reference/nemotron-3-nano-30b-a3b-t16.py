"""Plain reference of the transfer-stream ranker's train step
(configuration ``nemotron-3-nano-30b-a3b-t16``): the Nemotron-3-Nano
decoder as
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json
sizes it, its first nine layers (``MEMEM*EME``), a sixteenth of each
expert layer's routed experts, an eighth of the vocabulary.

float32 at ``highest`` precision, nothing of the program imported.  A
batch is ``rows`` sequences of ``positions`` records; a segment is a run
of equal child inside a row.  One row [L, D] goes through at a time, each
layer ``out = x + part(rms(x))`` with the part its letter's:

    M  [z | xBC | dt] = h W_in;  xBC = silu(conv(xBC) + bias)   causal, in the segment
       [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
       S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T  (0 at a segment's start)
       y_t = S_t C_t + D x_t                                   a head; B, C its group's
       part = (w * groupnorm(y * silu(z))) W_out                RMS over each group
    *  part = softmax(q k^T / sqrt(d)) v W_o                    s <= t in t's segment,
                                                                 no positions
    E  s = sigmoid(h W_r);  E = the k largest of s + b;  g_e = scale * s_e / sum_E s
       part = sum_{e in E, held here} g_e W_down,e relu(W_up,e h)^2
              + W_down,s relu(W_up,s h)^2

and after each step, each expert layer's selection bias moves:
b_e += selection_bias_rate * sign(mean load - load_e), the loads of all
the experts over the step's rows.  The SSD is its recurrence, token by
token (a loop over blocks of tokens, each recomputed in its backward):
never the program's chunked form.  Attention is a softmax over the whole
row for a block of queries under the masks; the expert layer a loop over
the experts held here, each over every token under its weight.  The
absent experts' part is left out, as in the program: the same share.

Read from the source, and assumed where it is silent (the configuration's
file lists the same): no positions in attention; the gated norm over the
8 groups of 512; sigmoid scores with the selection bias, n_group 1 and
topk_group 1 leaving the top-k over all experts; the norm x / rms(x) *
(1 + w), w = 0 at the start; the bias's rate 0.001 and its loads this
chip's batch's.

Departures from the published model, the program's too:
  1. the input adapter ``w_in`` over [hop[src], hop[dst], previous
     target], added to the token embedding (tokens are parent host ids);
     the hop features standardised by the snapshot's columns and the
     previous target by two constants of the configuration;
  2. Huber loss on one gathered logit (the record's own parent's, from
     the history up to the previous transfer) in place of next-token
     cross-entropy;
  3. a cold-start head from features alone at a segment's first record;
  4. no auxiliary router loss;
  5. initialisation: normal(0.02) for matrices and the embedding, norms
     at their identity, biases 0, and Mamba-2's own conv taps (uniform in
     +-1 / sqrt(4)), A_log, D and dt_bias.

AdamW is followed for the dispatch's steps a leaf at a time with both
moments kept on the host between steps, so that what the device holds at
once is the parameters, the summed gradient and one row's gradient and
temporaries.
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
SCAN_BLOCK = 128          # tokens of the SSD's recurrence recomputed together in the backward


def kinds(m: dict) -> str:
    return m["hybrid_override_pattern"]


def expert_layers(m: dict):
    return [i for i, k in enumerate(kinds(m)) if k == "E"]


# -- parameters, from the seed ----------------------------------------------------


def parameter_list(m: dict, hop_dim: int, n: int):
    """(name, kind, shape) in the order the flax module declares them: the
    order is the key each one is drawn with."""
    d, h, kv, hd = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    nh, ng, ns = m["mamba_num_heads"], m["n_groups"], m["ssm_state_size"]
    di = nh * m["mamba_head_dim"]
    e, f, fs = m["num_experts_held"], m["moe_intermediate_size"], m["moe_shared_expert_intermediate_size"]
    out = [
        ("w_in", "normal", (2 * hop_dim + 1, d)),
        ("final_norm", "zeros", (d,)),
        ("head", "normal", (n, d)),
        ("cold.kernel", "normal", (2 * hop_dim, 1)),
        ("cold.bias", "zeros", (1,)),
    ]
    for i, kind in enumerate(kinds(m)):
        pre = f"layer_{i}."
        if kind == "M":
            out += [
                (pre + "mamba.w_in", "normal", (d, 2 * di + 2 * ng * ns + nh)),
                (pre + "mamba.conv", "conv", (m["conv_kernel"], di + 2 * ng * ns)),
                (pre + "mamba.conv_bias", "zeros", (di + 2 * ng * ns,)),
                (pre + "mamba.dt_bias", "dt_bias", (nh,)),
                (pre + "mamba.A_log", "a_log", (nh,)),
                (pre + "mamba.D", "ones", (nh,)),
                (pre + "mamba.norm", "ones", (di,)),
                (pre + "mamba.w_out", "normal", (di, d)),
                (pre + "norm1", "zeros", (d,)),
            ]
        elif kind == "*":
            out += [
                (pre + "attn.w_q", "normal", (d, h * hd)),
                (pre + "attn.w_k", "normal", (d, kv * hd)),
                (pre + "attn.w_v", "normal", (d, kv * hd)),
                (pre + "attn.w_o", "normal", (h * hd, d)),
                (pre + "norm1", "zeros", (d,)),
            ]
        else:
            out += [
                (pre + "norm2", "zeros", (d,)),
                (pre + "moe.router", "normal", (d, m["n_routed_experts"])),
                (pre + "moe.w_up", "normal", (e, d, f)),
                (pre + "moe.w_down", "normal", (e, f, d)),
                (pre + "moe.shared.w_up", "normal", (d, fs)),
                (pre + "moe.shared.w_down", "normal", (fs, d)),
            ]
    return out


def _draw(kind: str, key, shape, m: dict):
    if kind == "normal":
        return 0.02 * jax.random.normal(key, shape, F32)
    if kind == "ones":
        return jnp.ones(shape, F32)
    if kind == "conv":                     # PyTorch's Conv1d: +-1 / sqrt(fan-in of a channel)
        return jax.random.uniform(key, shape, F32, -shape[0] ** -0.5, shape[0] ** -0.5)
    if kind == "a_log":                    # A uniform in [1, 16]
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    if kind == "dt_bias":                  # softplus^-1 of a log-uniform time step
        lo, hi = np.log(m["time_step_min"]), np.log(m["time_step_max"])
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, F32, lo, hi)), m["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))
    return jnp.zeros(shape, F32)


def init_params(seed_key, m: dict, hop_dim: int, n: int) -> dict:
    """{flattened name: array}, equal to the flax module's bit for bit: the
    module's own parameters take the keys 1, 2, ... of its scope in the
    order declared, the embedding the first key of its own."""
    params = {
        "embed/embedding": _draw("normal", C.flax_key(seed_key, "embed", 1), (n, m["hidden_size"]), m)
    }
    for count, (name, kind, shape) in enumerate(parameter_list(m, hop_dim, n), start=1):
        params[name] = _draw(kind, C.flax_key(seed_key, count), shape, m)
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


def delayed(x, by: int):
    """x [L, ...] delayed by ``by`` tokens, zeros in."""
    if by == 0:
        return x
    return jnp.concatenate([jnp.zeros((by, *x.shape[1:]), x.dtype), x[:-by]], 0)


def segment_of(dst):
    """(start [L], segment id [L], position in the segment [L]) of a row."""
    start = jnp.concatenate([jnp.ones((1,), bool), dst[1:] != dst[:-1]])
    idx = jnp.arange(dst.shape[0])
    return start, jnp.cumsum(start.astype(jnp.int32)), idx - jax.lax.cummax(jnp.where(start, idx, 0))


# -- the layers, one row [L, D] at a time ---------------------------------------------------


def ssd(x, dt, a, b, c, start):
    """The recurrence: x [L, H, P], dt [L, H], a [H], b, c [L, G, N] (a
    group's for its H / G heads), start [L] -> y [L, H, P] (no D term)."""
    l, h, _ = x.shape
    per = h // b.shape[1]
    b, c = jnp.repeat(b, per, axis=1), jnp.repeat(c, per, axis=1)      # [L, H, N]

    def step(s, xs):
        x_t, dt_t, b_t, c_t, first = xs
        s = jnp.where(first, 0.0, jnp.exp(dt_t * a))[:, None, None] * s
        s = s + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t, precision="highest")

    @jax.checkpoint
    def block(s, xs):
        return jax.lax.scan(step, s, xs)

    k = min(SCAN_BLOCK, l)
    fold = lambda t: t.reshape(l // k, k, *t.shape[1:])
    s0 = jnp.zeros((h, x.shape[2], b.shape[2]), F32)
    _, y = jax.lax.scan(block, s0, tuple(fold(t) for t in (x, dt, b, c, start)))
    return y.reshape(x.shape)


def mamba(p, x, start, pos, m: dict, variant: str):
    l = x.shape[0]
    nh, hp, ng, ns = m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"], m["ssm_state_size"]
    di = nh * hp
    zxbcdt = dense(x, p["w_in"], variant)
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di: 2 * di + 2 * ng * ns], zxbcdt[:, 2 * di + 2 * ng * ns:]
    conv = p["conv_bias"] + sum(
        jnp.where((pos >= j)[:, None], delayed(xbc, j), 0.0) * p["conv"][j] for j in range(m["conv_kernel"])
    )
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :di].reshape(l, nh, hp)
    b = xbc[:, di: di + ng * ns].reshape(l, ng, ns)
    c = xbc[:, di + ng * ns:].reshape(l, ng, ns)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssd(xs, dt, -jnp.exp(p["A_log"]), b, c, start) + p["D"][:, None] * xs
    y = (y.reshape(l, di) * jax.nn.silu(z)).reshape(l, ng, di // ng)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + m["norm_eps"])
    return dense(y.reshape(l, di) * p["norm"], p["w_out"], variant)


def attention(p, x, seg, m: dict, variant: str):
    """Every query head against its key head's keys, no positions."""
    l = x.shape[0]
    h, kv, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q = dense(x, p["w_q"], variant).reshape(l, h, d)
    k = jnp.repeat(dense(x, p["w_k"], variant).reshape(l, kv, d), h // kv, axis=1)
    v = jnp.repeat(dense(x, p["w_v"], variant).reshape(l, kv, d), h // kv, axis=1)
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
    o = jax.lax.map(rows_of_queries, (fold(q), fold(seg), fold(idx))).reshape(l, h * d)
    return dense(o, p["w_o"], variant)


def relu2_mlp(p, x, variant: str):
    return dense(jnp.square(jax.nn.relu(dense(x, p["w_up"], variant))), p["w_down"], variant)


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
    def one(w_e, up, down):
        """One held expert over every token; made again in the backward."""
        return w_e[:, None] * relu2_mlp({"w_up": up, "w_down": down}, x, variant)

    y, _ = jax.lax.scan(
        lambda y, xs: (y + one(*xs), None), jnp.zeros_like(x), (held.T, p["w_up"], p["w_down"])
    )
    return y + relu2_mlp(p["shared"], x, variant), chosen.sum(0).astype(jnp.int32)


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


def block(lp, x, start, seg, pos, bias, m: dict, kind: str, variant: str):
    """(out, slots routed to each expert, zeros for a layer of no experts)."""
    eps = m["norm_eps"]
    none = jnp.zeros((m["n_routed_experts"],), jnp.int32)
    if kind == "M":
        return x + mamba(lp["mamba"], rms(x, lp["norm1"], eps), start, pos, m, variant), none
    if kind == "*":
        return x + attention(lp["attn"], rms(x, lp["norm1"], eps), seg, m, variant), none
    y, loads = expert_layer(lp["moe"], rms(x, lp["norm2"], eps), bias, m, variant)
    return x + y, loads


def row_predictions(flat_params, table, src, dst, prev_y, biases, m: dict, variant: str):
    """One row of L records -> (the L predictions, the slots routed to
    each expert by expert layer [expert layers, experts]).  ``table`` is
    ``standard_table``'s, ``prev_y`` a row of ``previous_targets``',
    ``biases`` [expert layers, experts] the selection biases."""
    p = nest(flat_params)
    start, seg, pos = segment_of(dst)
    feats = jnp.concatenate([table[src], table[dst]], -1)
    x = p["embed"]["embedding"][src] + dense(
        jnp.concatenate([feats, prev_y[:, None]], -1), p["w_in"], variant
    )
    experts, loads = expert_layers(m), []
    for i, kind in enumerate(kinds(m)):
        bias = biases[experts.index(i)] if kind == "E" else None
        x, n = jax.checkpoint(functools.partial(block, m=m, kind=kind, variant=variant))(
            p[f"layer_{i}"], x, start, seg, pos, bias
        )
        if kind == "E":
            loads.append(n)
    h = rms(x, p["final_norm"], m["norm_eps"])
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
