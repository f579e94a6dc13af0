"""Transfer-stream ranker: a child peer's transfers in arrival order
through a published decoder, its layers told by the configuration: the
Qwen3-Next one (Gated DeltaNet x3 : gated attention x1, 512 routed experts
top-10 plus a shared expert), the SmallThinker one (attention alone: a
window of 4,096 with RoPE x3 : the whole segment without positions x1, the
router read before attention, 64 ReGLU experts top-6, no shared expert),
the GLM-4.7-Flash one (latent attention (MLA) in every layer, a leading
dense SwiGLU layer, then 64 experts top-4 chosen by sigmoid score plus a
selection bias that the step itself moves, and an ungated shared expert)
and the Nemotron-3-Nano one (layers of one part each: a Mamba-2 mixer,
attention without positions, or 128 relu^2 experts top-6 by sigmoid score
and a selection bias beside a relu^2 shared expert).

A batch of ``B = rows x positions`` download records is read as ``rows``
sequences; a **segment** is a maximal run of equal ``dst`` inside a row
(streams of varying length packed back to back).  The head's output over
the host vocabulary is the predicted log-bandwidth from each host to the
child for its next transfer; in training only the record's own parent's
column is computed:

    x_t    = E[src_t] + W_in [hop[src_t], hop[dst_t], y_{t-1}]      (standardised)
    h      = blocks(x)                (h + mixer(rms(h)); h + moe(rms(h)),
                                       either part absent where the layer has none)
    pred_t = rms(h_{t-1}) . W_head[src_t]              t-1 in t's segment
    pred_t = w_cold . [hop[src_t], hop[dst_t]] + b     at a segment's start

Layer equations, sizes and the source are in
``benchmark/configs/<configuration>.json``; the float32 reference that
follows them token by token is ``benchmark/reference/<configuration>.py``
(``qwen3-next-80b-a3b-t16``, ``smallthinker-21b-a3b-t4``,
``glm-4-7-flash-t8``, ``nemotron-3-nano-30b-a3b-t16``).  Activations are
``config.dtype`` (bfloat16 on the chip); parameters, softmax, norms, gates,
the decay and the recurrent state are float32.

One chip holds one expert-parallel share of every layer:
``experts_held = (first, count)``.  The router is as wide as published and
the top-k and its normalisation are over all experts; what the absent
experts would add is left out and the partial result goes on.  No slot is
dropped: the slots of held experts are sorted by expert and multiplied
group by group (``ops/grouped_matmul.py``'s kernels on a TPU,
``jax.lax.ragged_dot`` elsewhere) in blocks of ``B`` slots:
``expert_blocks`` of them whatever the routing, and as many more as the
routing of that step fills.

Same call signature as ``HopRanker``.  The step's own extras (token-slots
each held expert received, slots routed, keys the attention layers'
queries attended and keys their bands hold by position, block pairs their
loops run and block pairs those bands hold, and where the expert layers
hold a selection bias the slots routed to each of all the experts) are
sown into the ``aux`` collection.  The selection biases live in a
collection of their own (``SELECTION_BIAS``), which the trainer carries
from step to step beside the parameters (``TrainState.model_state``).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops import attention_bwd, delta_scan, grouped_matmul, slot_rows
from ..ops.attention_bwd import block_scores as _block_scores, cut as _cut

F32 = jnp.float32
_MASKED = -1e30


DELTANET, ATTENTION, MAMBA = "deltanet", "attention", "mamba"
NO_MIXER = "none"                    # a layer that is its feed-forward alone
# A layer's feed-forward: the expert layer, a dense SwiGLU, or none (a
# layer that is its mixer alone).
EXPERTS, DENSE, NO_FEED_FORWARD = "experts", "dense", "none"
WINDOW, FULL = "window", "full"      # an attention layer's kind, as the counters label it
ATTENTION_KINDS = (WINDOW, FULL)
# What the step counts of its attention layers, each [window layers, full
# layers]: ``attention_keys``' two and ``attention_pairs``' two, as ``aux``
# names them.
ATTENTION_COUNTS = ("attn_keys_attended", "attn_keys_in_band", "attn_pairs_run", "attn_pairs_in_band")
# The collection that holds the expert layers' selection biases.
SELECTION_BIAS = "selection_bias"


@dataclass(frozen=True)
class Mixer:
    """One layer's mixer: Gated DeltaNet, Mamba-2, none, or attention over
    the causal part of the query's segment: the last ``window`` records of
    it, the query among them (0: all of it), turned by RoPE or without
    positions."""

    kind: str = ATTENTION
    window: int = 0
    rope: bool = True

    @property
    def attention_kind(self) -> str:
        return WINDOW if self.window else FULL


@dataclass(frozen=True)
class StreamRankerConfig:
    hidden_size: int = 2048
    num_hidden_layers: int = 4
    # Each layer's mixer; left out, Qwen3-Next's pattern: every
    # ``full_attention_interval``-th layer attention, the rest DeltaNet.
    layers: Optional[Tuple[Mixer, ...]] = None
    full_attention_interval: int = 4
    # Each layer's feed-forward (EXPERTS, DENSE or NO_FEED_FORWARD); left
    # out, DENSE for the first ``first_k_dense_replace`` layers, EXPERTS after.
    feed_forwards: Optional[Tuple[str, ...]] = None
    rms_norm_eps: float = 1e-6
    # attention
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    attention_gate: bool = True       # o * sigmoid(gate), the gate beside q in w_q
    qk_norm: bool = True              # RMS norm of every head's q and k
    # Latent attention (MLA) where kv_lora_rank is not 0: queries and
    # keys-values each through a low-rank latent, a head's q and k of
    # qk_nope_head_dim + qk_rope_head_dim dims (the rope part one key shared
    # by every head), its v of v_head_dim; head_dim and the gate, the q/k
    # norm and partial_rotary_factor above are then not read.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # gated DeltaNet
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # Mamba-2 (SSD): heads of mamba_head_dim, their B and C shared by the
    # heads of each of mamba_n_groups groups, a state of ssm_state_size a
    # head's dim; the gated norm over each group's heads.  The time step's
    # bias is drawn so that softplus(dt_bias) lies in [time_step_min,
    # time_step_max], time_step_floor at least.
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    mamba_n_groups: int = 8
    ssm_state_size: int = 128
    mamba_conv_kernel: int = 4
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # experts
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512    # 0: no shared expert
    shared_expert_gate: bool = True               # the shared expert's output under sigmoid(x . w)
    norm_topk_prob: bool = True
    # The experts' gate activation, or "relu2": relu(x)^2 of an ungated
    # expert, down(relu(up x)^2), routed and shared alike.
    hidden_act: str = "silu"
    # The k largest logits first and softmax over those, in place of
    # softmax over all experts and its k largest renormalised.
    softmax_after_topk: bool = False
    # "sigmoid": each expert's score is sigmoid(logit) in place of the
    # softmax over all experts.
    scoring_func: str = "softmax"
    routed_scaling_factor: float = 1.0            # the routed experts' weights times this
    # Where not 0, each expert layer holds a selection bias b [experts]:
    # the k largest of score + b are taken, weighted by the score alone;
    # no gradient reaches b, and after each step b_e += rate * sign(mean
    # load - load_e) over the step's loads of all the experts (the
    # auxiliary-loss-free balancing rule).
    selection_bias_rate: float = 0.0
    # The first this many layers have a dense SwiGLU of intermediate_size
    # in place of the expert layer.
    first_k_dense_replace: int = 0
    intermediate_size: int = 0
    # The router reads the block's first norm (the mixer's input) in place
    # of its second (the experts').
    router_before_attention: bool = False
    experts_held: Tuple[int, int] = (0, 32)    # (first, count) living here
    # the stream and the snapshot it reads
    positions: int = 4096
    hops: int = 2
    # What the previous target is standardised by: constants, as a served
    # model would have them (log1p of bytes/s: e^17 is 24 MB/s).
    target_center: float = 17.0
    target_scale: float = 1.0
    # how the timed path computes, not what
    expert_blocks: int = 6    # blocks of B slots every expert layer runs, filled or not
    chunk: int = 64           # the delta rule's and the SSD's chunk
    attn_block: int = 512     # attention's query and key blocks
    dtype: jnp.dtype = jnp.bfloat16


def layer_kinds(cfg: StreamRankerConfig) -> Tuple[Mixer, ...]:
    """The mixer of each of the configuration's layers."""
    if cfg.layers is None:
        return tuple(
            Mixer(ATTENTION if (i + 1) % cfg.full_attention_interval == 0 else DELTANET)
            for i in range(cfg.num_hidden_layers)
        )
    if len(cfg.layers) != cfg.num_hidden_layers:
        raise ValueError(f"{len(cfg.layers)} layer kinds for {cfg.num_hidden_layers} layers")
    return cfg.layers


def feed_forwards(cfg: StreamRankerConfig) -> Tuple[str, ...]:
    """The feed-forward of each of the configuration's layers."""
    if cfg.feed_forwards is None:
        return tuple(
            DENSE if i < cfg.first_k_dense_replace else EXPERTS for i in range(cfg.num_hidden_layers)
        )
    if len(cfg.feed_forwards) != cfg.num_hidden_layers:
        raise ValueError(f"{len(cfg.feed_forwards)} feed-forwards for {cfg.num_hidden_layers} layers")
    return cfg.feed_forwards


def expert_layers(cfg: StreamRankerConfig) -> Tuple[int, ...]:
    """The layers whose feed-forward is the expert layer."""
    return tuple(i for i, feed in enumerate(feed_forwards(cfg)) if feed == EXPERTS)


# Each letter of a ``hybrid_override_pattern`` (nemotron_h): a layer that is
# one part alone.  ("-", its dense relu^2 MLP, is a layer the program lacks.)
_PATTERN = {
    "M": (Mixer(MAMBA), NO_FEED_FORWARD),
    "*": (Mixer(ATTENTION, rope=False), NO_FEED_FORWARD),
    "E": (Mixer(NO_MIXER), EXPERTS),
}


def hybrid_layers(pattern: str) -> Tuple[Tuple[Mixer, ...], Tuple[str, ...]]:
    """(each layer's mixer, each layer's feed-forward) of a hybrid pattern:
    ``M`` Mamba-2 alone, ``*`` attention without positions alone, ``E``
    the expert layer alone."""
    unknown = sorted(set(pattern) - set(_PATTERN))
    if unknown:
        raise ValueError(f"no layer for {unknown} of the pattern {pattern!r}: the program has {sorted(_PATTERN)}")
    mixers, feeds = zip(*(_PATTERN[letter] for letter in pattern))
    return mixers, feeds


# -- the stream's axis ----------------------------------------------------------


def segments(dst: jax.Array, positions: int):
    """``dst`` [B] -> (start [R, L] bool, segment id [R, L], position in
    the segment [R, L]): a segment starts at a row's first record and
    wherever the child changes."""
    with jax.named_scope("stream/segments"):
        d = dst.reshape(-1, positions)
        start = jnp.concatenate(
            [jnp.ones((d.shape[0], 1), bool), d[:, 1:] != d[:, :-1]], axis=1
        )
        seg = jnp.cumsum(start.astype(jnp.int32), axis=1)
        idx = jnp.arange(positions, dtype=jnp.int32)
        pos = idx - jax.lax.cummax(jnp.where(start, idx, 0), axis=1)
    return start, seg, pos


def previous_target(dst: jax.Array, y: jax.Array, positions: int) -> jax.Array:
    """The query feature the trainer builds: [B, 1], the previous record's
    target in the same segment, 0 at a segment's first record.  The model
    never sees a record's own target."""
    start, _, _ = segments(dst, positions)
    with jax.named_scope("stream/embed"):
        y = y.reshape(start.shape).astype(F32)
        prev = jnp.pad(y, ((0, 0), (1, 0)))[:, :-1]
        return jnp.where(start, 0.0, prev).reshape(-1, 1)


def standard_inputs(hop_feats, src, dst, prev, start, cfg: "StreamRankerConfig"):
    """What the adapter and the cold-start head read: the two hosts' hop
    features standardised by the snapshot's own columns, and the previous
    target by the configuration's two constants, nought where there is
    none: nothing of the batch, so a record's input is made of its own
    stream's past and the snapshot alone.  Raw, the features' common part
    (log counts near 8, a log-bandwidth near 17) is most of every record's
    input, every record looks alike after the first norm and each layer's
    router sends all of them to the same ten experts."""
    table = hop_feats.astype(F32)
    table = (table - table.mean(0)) / (table.std(0) + 1e-3)
    feats = jnp.concatenate([jnp.take(table, src, axis=0), jnp.take(table, dst, axis=0)], -1)
    prev = (prev.astype(F32) - cfg.target_center) / cfg.target_scale
    return feats, jnp.where(start, 0.0, prev)


def _shift(x: jax.Array, by: int) -> jax.Array:
    """``x`` [R, L, ...] delayed by ``by`` positions along L, zeros in."""
    if by == 0:
        return x
    pad = [(0, 0), (by, 0)] + [(0, 0)] * (x.ndim - 2)
    return jnp.pad(x, pad)[:, : x.shape[1]]


def rms(x, w, eps):
    """Zero-centred RMS norm over the last axis: x / rms(x) * (1 + w)."""
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w)).astype(x.dtype)


def _mm(x, w, dtype):
    """Activations x weights on the MXU: operands in ``dtype``, float32
    accumulation, result in ``dtype``."""
    return jnp.dot(x.astype(dtype), w.astype(dtype), preferred_element_type=F32).astype(dtype)


# -- Gated DeltaNet ----------------------------------------------------------------


_HIGHEST = jax.lax.Precision.HIGHEST
_INVERSE_BASE = 16


def _inverse_by_substitution(a):
    """(I + A)^-1 row by row: row i of T is e_i - A[i, :i] T[:i]."""
    c = a.shape[-1]
    unit = lambda i: jnp.zeros(a.shape[:-2] + (c,), a.dtype).at[..., i].set(1.0)
    rows = [unit(0)]
    for i in range(1, c):
        done = jnp.stack(rows, axis=-2)
        rows.append(unit(i) - jnp.einsum("...j,...jk->...k", a[..., i, :i], done, precision=_HIGHEST))
    return jnp.stack(rows, axis=-2)


@jax.custom_vjp
def unit_lower_inverse(a):
    """(I + A)^-1 for strictly lower triangular ``a`` [..., C, C].

    Diagonal blocks of 16 by forward substitution, then pairs of blocks
    merged, inv [[P, 0], [Q, S]] = [[P', 0], [-S' Q P', S']], until one
    block is left: every product is of true inverses, whose entries the
    delta rule bounds by one.  (The Neumann series, (I - A)(I + A^2)
    (I + A^4)..., is fewer and larger products but forms A's powers: keys
    that share a direction, as they do behind a SiLU, take their entries
    to 1e15, float32 cancels them to noise, and the chip read NaN in this
    PR's first run.)"""
    c = a.shape[-1]
    b = min(_INVERSE_BASE, c)
    if c % b or (c // b) & (c // b - 1):
        return _inverse_by_substitution(a)
    lead = a.shape[:-2]

    def diagonal(of, size, part):
        """The blocks on the diagonal of ``of`` cut into ``size`` x
        ``size``, each cut down by ``part``: [..., C / size, ., .]."""
        n = c // size
        grid = of.reshape(*lead, n, size, n, size)
        return jnp.stack([grid[..., i, :, i, :][(..., *part)] for i in range(n)], axis=-3)

    t = _inverse_by_substitution(diagonal(a, b, (slice(None), slice(None))))
    m = b
    while m < c:
        q = diagonal(a, 2 * m, (slice(m, None), slice(None, m)))
        p, s = t[..., 0::2, :, :], t[..., 1::2, :, :]
        low = -jnp.matmul(jnp.matmul(s, q, precision=_HIGHEST), p, precision=_HIGHEST)
        t = jnp.concatenate(
            [jnp.concatenate([p, jnp.zeros_like(p)], -1), jnp.concatenate([low, s], -1)], -2
        )
        m *= 2
    return t[..., 0, :, :]


def _uli_fwd(a):
    t = unit_lower_inverse(a)
    return t, t


def _uli_bwd(t, g):
    tt = jnp.swapaxes(t, -1, -2)
    return (-(tt @ g @ tt),)


unit_lower_inverse.defvjp(_uli_fwd, _uli_bwd)


def delta_rule_recurrent(q, k, v, g, beta, start):
    """The recurrence itself, token by token (float32): q, k [R, L, H, dk],
    v [R, L, H, dv], g, beta [R, L, H], start [R, L].  What the chunked
    form is tested against; not on the timed path."""
    r, _, h, dk = q.shape
    dv = v.shape[-1]

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t, first = xs
        s = s * jnp.where(first, 0.0, jnp.exp(g_t))[:, :, None, None]
        u = b_t[..., None] * (v_t - jnp.einsum("rhkv,rhk->rhv", s, k_t))
        s = s + k_t[..., :, None] * u[..., None, :]
        return s, jnp.einsum("rhkv,rhk->rhv", s, q_t)

    lead = lambda a: jnp.moveaxis(a.astype(F32), 1, 0)
    first = jnp.moveaxis(start, 1, 0)[:, :, None]
    _, o = jax.lax.scan(
        step, jnp.zeros((r, h, dk, dv), F32),
        (lead(q), lead(k), lead(v), lead(g), lead(beta), first),
    )
    return jnp.moveaxis(o, 0, 1)


def delta_rule_chunked(q, k, v, g, beta, start, seg, chunk: int, dtype):
    """The same recurrence in its chunked form.  q, k [R, L, Hk, dk] (each
    key head serves ``G`` value heads), v [R, L, Hk, G, dv], g, beta
    [R, L, Hk, G] float32, start / seg [R, L].  Returns [R, L, Hk, G, dv]
    float32.

    Inside a chunk of C tokens the C updates are one triangular system,
    U = (I + A)^-1 (beta V - beta P K S0), with A_ij = beta_i D_ij k_i.k_j
    below the diagonal; D_ij is the decay from j to i, nought across a
    segment's start, and P_i the decay from the chunk's start to i,
    nought once a segment has started in the chunk.  The state is carried
    from chunk to chunk in float32."""
    r, l, hk, dk = q.shape
    grp, dv = v.shape[3], v.shape[4]
    c = min(chunk, l)
    if l % c:
        raise ValueError(f"positions {l} is not a multiple of the chunk {c}")
    n = l // c

    # [R, L, ...] -> [R, N, C, ...] -> heads before the chunk axes.
    cut = lambda a: a.reshape(r, n, c, *a.shape[2:])
    qc = jnp.moveaxis(cut(q), 3, 1)                      # [R, Hk, N, C, dk]
    kc = jnp.moveaxis(cut(k), 3, 1)
    vc = jnp.moveaxis(cut(v), (3, 4), (1, 2))            # [R, Hk, G, N, C, dv]
    gc = jnp.moveaxis(cut(g), (3, 4), (1, 2))            # [R, Hk, G, N, C]
    bc = jnp.moveaxis(cut(beta), (3, 4), (1, 2))
    startc, segc = cut(start), cut(seg)                  # [R, N, C]

    # The decay at a segment's first record multiplies a state that is
    # reset there: it is left out of the sums and the masks do the reset.
    gc = jnp.where(startc[:, None, None], 0.0, gc)
    d = jnp.cumsum(gc, axis=-1)                          # [R, Hk, G, N, C]
    same = segc[..., :, None] == segc[..., None, :]      # [R, N, C, C]
    lower = jnp.tril(jnp.ones((c, c), bool))
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    cont = jnp.cumsum(startc.astype(jnp.int32), axis=-1) == 0      # [R, N, C]
    same_last = segc == segc[..., -1:]                   # [R, N, C]

    diff = d[..., :, None] - d[..., None, :]             # [R, Hk, G, N, C, C]
    mask = (same & lower)[:, None, None]
    decay = jnp.exp(jnp.where(mask, diff, _MASKED))      # D, diagonal included
    kk = jnp.einsum("rhncd,rhnsd->rhncs", kc, kc, preferred_element_type=F32)
    qk = jnp.einsum("rhncd,rhnsd->rhncs", qc, kc, preferred_element_type=F32)
    a = jnp.where(strict, bc[..., None] * decay * kk[:, :, None], 0.0)
    t = unit_lower_inverse(a).astype(dtype)              # [R, Hk, G, N, C, C]
    attn = (decay * qk[:, :, None]).astype(dtype)

    p = jnp.where(cont[:, None, None], jnp.exp(d), 0.0)  # [R, Hk, G, N, C]
    kf = kc.astype(F32)[:, :, None]                      # [R, Hk, 1, N, C, dk]
    bv = (bc[..., None] * vc.astype(F32)).astype(dtype)
    bpk = ((bc * p)[..., None] * kf).astype(dtype)
    u = jnp.einsum("rhgncs,rhgnsd->rhgncd", t, bv, preferred_element_type=F32)
    w = jnp.einsum("rhgncs,rhgnsd->rhgncd", t, bpk, preferred_element_type=F32).astype(dtype)
    qp = (p[..., None] * qc.astype(F32)[:, :, None]).astype(dtype)
    # What each token's update leaves in the state at the chunk's end.
    tail = jnp.exp(jnp.where(same_last[:, None, None], d[..., -1:] - d, _MASKED))
    kt = (tail[..., None] * kf).astype(dtype)            # [R, Hk, G, N, C, dk]
    keep = p[..., -1]                                    # [R, Hk, G, N]

    # The loop over the chunks, the state carried in float32: on a TPU by
    # ops/delta_scan.py's kernels, which keep it on the chip; a lax.scan
    # anywhere else.
    carrier = delta_scan.scan_carrier(dtype, dk, dv, c)
    return delta_scan.chunk_scan(u, w, qp, attn, kt, keep, carrier)


def gated_delta_net(p, x, start, seg, pos, cfg: StreamRankerConfig):
    """x [R, L, D] -> [R, L, D]."""
    dtype = cfg.dtype
    r, l, _ = x.shape
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    grp = hv // hk
    with jax.named_scope("stream/gdn/proj"):
        qkvz = _mm(x, p["w_qkvz"], dtype)
        ba = jnp.dot(x.astype(F32), p["w_ba"], precision=jax.lax.Precision.HIGHEST)
        qkv, z = qkvz[..., : 2 * hk * dk + hv * dv], qkvz[..., 2 * hk * dk + hv * dv:]
        b, a = ba[..., :hv], ba[..., hv:]
    with jax.named_scope("stream/gdn/conv"):
        # Causal depthwise conv; tap j reads t - j, and not across a
        # segment's start.
        conv = p["conv"].astype(F32)
        acc = 0.0
        for j in range(cfg.linear_conv_kernel_dim):
            tap = jnp.where((pos >= j)[..., None], _shift(qkv, j).astype(F32), 0.0)
            acc = acc + tap * conv[j]
        qkv = jax.nn.silu(acc)
        q = qkv[..., : hk * dk].reshape(r, l, hk, dk)
        k = qkv[..., hk * dk: 2 * hk * dk].reshape(r, l, hk, dk)
        v = qkv[..., 2 * hk * dk:].reshape(r, l, hk, grp, dv).astype(dtype)
        l2 = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
        q = (l2(q) * dk ** -0.5).astype(dtype)
        k = l2(k).astype(dtype)
    with jax.named_scope("stream/gdn/scan"):
        beta = jax.nn.sigmoid(b).reshape(r, l, hk, grp)
        g = (-jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])).reshape(r, l, hk, grp)
        o = delta_rule_chunked(q, k, v, g, beta, start, seg, cfg.chunk, dtype)
    with jax.named_scope("stream/gdn/out"):
        # The gated norm, over each head's dv: w x / rms(x) * silu(z).
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        o = (o * p["norm"]).reshape(r, l, hv * dv) * jax.nn.silu(z.astype(F32))
        return _mm(o, p["w_o"], dtype)


# -- Mamba-2 -----------------------------------------------------------------------------


def ssd_recurrent(c, b, v, g, start):
    """The SSD recurrence token by token (float32): for each head,
    ``S_t = exp(g_t) S_{t-1} + b_t v_t^T`` (nought before a segment's
    start) and ``y_t = S_t^T c_t``.  c, b [R, L, Hk, dk] (a group's, shared
    by its ``G`` heads), v [R, L, Hk, G, dv], g [R, L, Hk, G], start [R, L].
    What the chunked form is tested against; not on the timed path."""
    r, _, hk, dk = c.shape
    grp, dv = v.shape[3], v.shape[4]

    def step(s, xs):
        c_t, b_t, v_t, g_t, first = xs
        s = s * jnp.where(first, 0.0, jnp.exp(g_t))[..., None, None]
        s = s + b_t[:, :, None, :, None] * v_t[..., None, :]
        return s, jnp.einsum("rhgkv,rhk->rhgv", s, c_t)

    lead = lambda a: jnp.moveaxis(a.astype(F32), 1, 0)
    first = jnp.moveaxis(start, 1, 0)[:, :, None, None]
    _, y = jax.lax.scan(
        step, jnp.zeros((r, hk, grp, dk, dv), F32), (lead(c), lead(b), lead(v), lead(g), first)
    )
    return jnp.moveaxis(y, 0, 1)


def ssd_chunked(c, b, v, g, start, seg, chunk: int, dtype):
    """``ssd_recurrent`` in its chunked form: the delta rule's with no delta
    correction (``delta_rule_chunked`` with beta's system the identity).
    Inside a chunk, ``y_i = sum_{j <= i} D_ij (c_i . b_j) v_j + P_i c_i S0``
    with D_ij the decay from j to i, nought across a segment's start, and
    P_i the decay from the chunk's start to i, nought once a segment has
    started in the chunk; the state is carried from chunk to chunk in
    float32 by ``delta_scan.chunk_scan``.  Shapes as ``ssd_recurrent``'s,
    ``v`` and ``g`` float32; returns [R, L, Hk, G, dv] float32."""
    r, l, hk, dk = c.shape
    dv = v.shape[4]
    cl = min(chunk, l)
    if l % cl:
        raise ValueError(f"positions {l} is not a multiple of the chunk {cl}")
    n = l // cl
    cut = lambda a: a.reshape(r, n, cl, *a.shape[2:])
    cc, bc = jnp.moveaxis(cut(c), 3, 1), jnp.moveaxis(cut(b), 3, 1)      # [R, Hk, N, C, dk]
    vc = jnp.moveaxis(cut(v), (3, 4), (1, 2))                           # [R, Hk, G, N, C, dv]
    startc, segc = cut(start), cut(seg)                                 # [R, N, C]
    d = jnp.cumsum(jnp.where(startc[:, None, None], 0.0, jnp.moveaxis(cut(g), (3, 4), (1, 2))), -1)
    same = (segc[..., :, None] == segc[..., None, :]) & jnp.tril(jnp.ones((cl, cl), bool))
    decay = jnp.exp(jnp.where(same[:, None, None], d[..., :, None] - d[..., None, :], _MASKED))
    cb = jnp.einsum("rhncd,rhnsd->rhncs", cc, bc, preferred_element_type=F32)
    attn = (decay * cb[:, :, None]).astype(dtype)
    p = jnp.where((jnp.cumsum(startc.astype(jnp.int32), -1) == 0)[:, None, None], jnp.exp(d), 0.0)
    cp = (p[..., None] * cc.astype(F32)[:, :, None]).astype(dtype)
    tail = jnp.exp(jnp.where((segc == segc[..., -1:])[:, None, None], d[..., -1:] - d, _MASKED))
    bt = (tail[..., None] * bc.astype(F32)[:, :, None]).astype(dtype)
    carrier = delta_scan.scan_carrier(dtype, dk, dv, cl)
    return delta_scan.chunk_scan(vc, None, cp, attn, bt, p[..., -1], carrier)


def mamba_mixer(p, x, start, seg, pos, cfg: StreamRankerConfig):
    """x [R, L, D] -> [R, L, D]: Mamba-2 (SSD).

        [z | xBC | dt] = x W_in;  xBC = silu(conv(xBC) + bias)     causal, 4 taps
        [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(dt A) S_{t-1} + dt x_t B_t^T;  y_t = S_t^T C_t + D x_t   a head
        out = (w * gnorm(y * silu(z))) W_out      RMS over each group's heads

    A head's B and C are its group's; the conv's taps and the state stop
    at a segment's start.  Its three stages (projection and conv, the
    scan, the gated norm and projection out) are each recomputed in their
    own backward: a row's backward holds one stage's temporaries at a
    time (at 8,192 positions each is gigabytes)."""
    dtype, eps = cfg.dtype, cfg.rms_norm_eps
    r, l, _ = x.shape
    ng, hd, ds = cfg.mamba_n_groups, cfg.mamba_head_dim, cfg.ssm_state_size
    grp = cfg.mamba_num_heads // ng
    di = cfg.mamba_num_heads * hd

    @jax.checkpoint
    def project(p, x, pos):
        with jax.named_scope("stream/mamba/proj"):
            zxbcdt = _mm(x, p["w_in"], dtype)
            z, xbc, dt = zxbcdt[..., :di], zxbcdt[..., di: 2 * di + 2 * ng * ds], zxbcdt[..., 2 * di + 2 * ng * ds:]
        with jax.named_scope("stream/mamba/conv"):
            # Causal depthwise conv; tap j reads t - j, and not across a
            # segment's start.
            conv = p["conv"].astype(F32)
            acc = p["conv_bias"]
            for j in range(cfg.mamba_conv_kernel):
                tap = jnp.where((pos >= j)[..., None], _shift(xbc, j).astype(F32), 0.0)
                acc = acc + tap * conv[j]
            xbc = jax.nn.silu(acc)
            xs = xbc[..., :di].reshape(r, l, ng, grp, hd)
            b = xbc[..., di: di + ng * ds].reshape(r, l, ng, ds).astype(dtype)
            c = xbc[..., di + ng * ds:].reshape(r, l, ng, ds).astype(dtype)
        return z, xs, b, c, dt

    @jax.checkpoint
    def scan(p, xs, b, c, dt, start, seg):
        with jax.named_scope("stream/mamba/scan"):
            dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"]).reshape(r, l, ng, grp)
            a = -jnp.exp(p["A_log"]).reshape(ng, grp)
            y = ssd_chunked(c, b, dt[..., None] * xs, dt * a, start, seg, cfg.chunk, dtype)
            return y + p["D"].reshape(ng, grp, 1) * xs

    @jax.checkpoint
    def out(p, y, z):
        with jax.named_scope("stream/mamba/out"):
            # The gated norm: gate first, then RMS over each group's heads.
            y = y.reshape(r, l, ng, grp * hd) * jax.nn.silu(z.astype(F32)).reshape(r, l, ng, grp * hd)
            y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
            return _mm(y.reshape(r, l, di) * p["norm"], p["w_out"], dtype)

    z, xs, b, c, dt = project(p, x, pos)
    return out(p, scan(p, xs, b, c, dt, start, seg), z)


# -- gated attention -----------------------------------------------------------------


def _rope(x, positions: int, rotary: int, theta: float):
    """Rotary embedding on the first ``rotary`` of the head's dims (the
    halves convention), positions counted along the row.  x [R, L, H, d]."""
    inv = 1.0 / (theta ** (np.arange(0, rotary, 2, dtype=np.float64) / rotary))
    ang = np.arange(positions, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(ang), np.cos(ang)], -1), F32)[None, :, None, :]
    sin = jnp.asarray(np.concatenate([np.sin(ang), np.sin(ang)], -1), F32)[None, :, None, :]
    rot, rest = x[..., :rotary].astype(F32), x[..., rotary:]
    half = rotary // 2
    turned = jnp.concatenate([-rot[..., half:], rot[..., :half]], -1)
    return jnp.concatenate([(rot * cos + turned * sin).astype(x.dtype), rest], -1)


def _band_start(i, blk: int, window: int):
    """The first key block of query block ``i``'s band by position: the
    one that holds the oldest key its first query sees under the window
    and the causal order.  By position alone; ``_segment_block`` is what
    the segments cut from it."""
    if not window:
        return jnp.zeros((), jnp.int32)
    return jnp.maximum(i * blk - (window - 1), 0) // blk


def _segment_block(seg, blk: int):
    """[R, L // blk]: for every row and query block, the key block that
    holds the start of the segment of the block's first query.  ``seg`` is
    non-decreasing along a row (``segments``: a cumulative sum), so that
    start is the count of positions whose ``seg`` is under the first
    query's; no later query of the block belongs to an older segment, and
    every key block before this one is masked whole for the block."""
    first = seg[:, ::blk]                                             # [R, L // blk]
    return jnp.sum(seg[:, None, :] < first[:, :, None], axis=-1, dtype=jnp.int32) // blk


def _first_key_blocks(seg, blk: int, window: int):
    """[R, L // blk]: the first key block a row's query block has to
    read: the later of the band's start by position and the segment's."""
    at = jnp.arange(seg.shape[1] // blk, dtype=jnp.int32)
    return jnp.maximum(_band_start(at, blk, window), _segment_block(seg, blk))


def _attention_fwd(q, k, v, seg, block: int, scale: float, window: int):
    """q [R, K, G, L, d], k, v [R, K, L, d], seg [R, L] -> (o, lse).  A loop
    over the blocks of queries, and inside it one over the blocks of keys
    of the query block's band, the diagonal first and back to the later of
    the band's start by position and the block that holds the start of the
    first query's segment (the least over the call's rows), softmax kept
    running in float32: no [L, L] is ever whole, and the program holds one
    body of a block pair whatever L is."""
    l = q.shape[3]
    blk = min(block, l)
    if l % blk:
        raise ValueError(f"positions {l} is not a multiple of attention's block {blk}")
    first = _first_key_blocks(seg, blk, window).min(0)                # [L // blk]

    def query_block(i, out):
        o, lse = out
        q_i, seg_i = _cut(q, i, blk, 3), _cut(seg, i, blk, 1)

        def pair(t, carry):
            m, den, acc = carry
            j = i - t
            s, ok = _block_scores(q_i, _cut(k, j, blk, 2), seg_i, _cut(seg, j, blk, 1), i, j, blk, scale, window)
            s = jnp.where(ok, s, _MASKED)
            m_new = jnp.maximum(m, s.max(-1))
            pr = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
            fix = jnp.exp(m - m_new)
            den = den * fix + pr.sum(-1)
            acc = acc * fix[..., None] + jnp.einsum(
                "rkgqs,rksd->rkgqd", pr.astype(v.dtype), _cut(v, j, blk, 2), preferred_element_type=F32
            )
            return m_new, den, acc

        m, den, acc = jax.lax.fori_loop(
            0, i - first[i] + 1, pair,
            (jnp.full(q_i.shape[:-1], _MASKED, F32), jnp.zeros(q_i.shape[:-1], F32), jnp.zeros(q_i.shape, F32)),
        )
        o = jax.lax.dynamic_update_slice_in_dim(o, acc / den[..., None], i * blk, 3)
        lse = jax.lax.dynamic_update_slice_in_dim(lse, m + jnp.log(den), i * blk, 3)
        return o, lse

    return jax.lax.fori_loop(
        0, l // blk, query_block, (jnp.zeros(q.shape, F32), jnp.zeros(q.shape[:-1], F32))
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def segment_attention(q, k, v, seg, block: int, scale: float, window: int = 0):
    """Causal softmax attention masked to the segment and, where ``window``
    is not 0, to the query's last ``window`` keys (itself among them),
    blockwise, with a backward that recomputes each block's probabilities
    from the row sums the forward kept.  ``seg`` is non-decreasing along a
    row.  A query block's key blocks outside its band by position are
    skipped, and so are those before the block that holds the start of its
    first query's segment: every score of such a pair is masked, so what
    is left out added exact zeros, and a row that is one segment runs the
    whole band."""
    return _attention_fwd(q, k, v, seg, block, scale, window)[0].astype(q.dtype)


def _sa_fwd(q, k, v, seg, block, scale, window):
    # The output is kept in float32 for the backward: each row's sum of
    # p . dp is taken from it, and dp less that sum cancels to the rounding
    # of whichever is coarser.
    o, lse = _attention_fwd(q, k, v, seg, block, scale, window)
    return o.astype(q.dtype), (q, k, v, seg, o, lse)


def _sa_bwd(block, scale, window, res, do):
    q, k, v, seg, o, lse = res
    blk = min(block, q.shape[3])
    run = attention_bwd.carrier(q.dtype, q.shape[4], q.shape[2], blk, q.shape[3])
    first = _first_key_blocks(seg, blk, window)
    dq, dk, dv = attention_bwd.backward(q, k, v, seg, o, lse, do, first, blk, scale, window, run)
    return dq, dk, dv, None


segment_attention.defvjp(_sa_fwd, _sa_bwd)


def attention_keys(pos, window: int):
    """(keys the queries of ``pos`` [R, L] attend under the segment, the
    causal order and the window; keys the band holds for them by position
    alone, were every row one segment), each summed over the records."""
    at = jnp.broadcast_to(jnp.arange(pos.shape[1], dtype=jnp.uint32), pos.shape)
    seen = lambda a: jnp.sum(jnp.minimum(a + 1, window) if window else a + 1)
    return seen(pos.astype(jnp.uint32)), seen(at)


def attention_pairs(seg, block: int, window: int):
    """(block pairs ``segment_attention``'s loop runs over ``seg`` [R, L]
    taken a row a call, as ``_row_by_row`` calls it; block pairs the bands
    hold by position alone), each summed over the rows."""
    blk = min(block, seg.shape[1])
    at = jnp.arange(seg.shape[1] // blk, dtype=jnp.int32)
    run = jnp.sum(at + 1 - _first_key_blocks(seg, blk, window))
    in_band = seg.shape[0] * jnp.sum(at + 1 - _band_start(at, blk, window))
    return run.astype(jnp.uint32), in_band.astype(jnp.uint32)


def gated_attention(p, x, seg, cfg: StreamRankerConfig, kind: Mixer):
    """x [R, L, D] -> [R, L, D]: softmax attention of ``kind``'s window and
    positions; the output gate and the heads' q/k norm where the
    configuration has them."""
    dtype = cfg.dtype
    r, l, _ = x.shape
    h, kv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    rotary = int(d * cfg.partial_rotary_factor)
    with jax.named_scope("stream/attn/proj"):
        q = _mm(x, p["w_q"], dtype).reshape(r, l, h, -1)
        if cfg.attention_gate:
            q, gate = q[..., :d], q[..., d:]
        k = _mm(x, p["w_k"], dtype).reshape(r, l, kv, d)
        v = _mm(x, p["w_v"], dtype).reshape(r, l, kv, d)
        if cfg.qk_norm:
            q, k = rms(q, p["q_norm"], cfg.rms_norm_eps), rms(k, p["k_norm"], cfg.rms_norm_eps)
        if kind.rope:
            q, k = _rope(q, l, rotary, cfg.rope_theta), _rope(k, l, rotary, cfg.rope_theta)
    with jax.named_scope("stream/attn/core"):
        # [R, L, H, d] -> [R, KV, G, L, d]: a key head's group of queries.
        qh = jnp.transpose(q.reshape(r, l, kv, h // kv, d), (0, 2, 3, 1, 4))
        kh, vh = jnp.transpose(k, (0, 2, 1, 3)), jnp.transpose(v, (0, 2, 1, 3))
        o = segment_attention(qh, kh, vh, seg, cfg.attn_block, d ** -0.5, kind.window)
        o = jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(r, l, h, d)
    with jax.named_scope("stream/attn/proj"):
        if cfg.attention_gate:
            o = o.astype(F32) * jax.nn.sigmoid(gate.astype(F32))
        return _mm(o.reshape(r, l, h * d), p["w_o"], dtype)


def latent_attention(p, x, seg, cfg: StreamRankerConfig, kind: Mixer):
    """x [R, L, D] -> [R, L, D]: latent attention (MLA) in its
    decompressed form, every head's keys and values made whole:

        c_q = rms_q(x W_qa);  [q_nope | q_pe] = c_q W_qb              a head
        [c_kv | k_pe] = x W_kva;  [k_nope | v] = rms_kv(c_kv) W_kvb   a head
        q = [RoPE(q_pe) | q_nope],  k = [RoPE(k_pe) | k_nope]

    ``k_pe`` is one head's, the same for every head.  A head's dims are
    held rope first, so that ``_rope`` turns the first ``qk_rope_head_dim``
    (the order is immaterial to q . k); softmax over ``kind``'s band at
    1 / sqrt(nope + rope)."""
    dtype, eps = cfg.dtype, cfg.rms_norm_eps
    r, l, _ = x.shape
    h, nope, rope, dv = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    if dv != nope + rope:
        raise ValueError(f"v_head_dim {dv} differs from the q/k head's {nope + rope}: segment_attention keeps one")
    with jax.named_scope("stream/attn/latent"):
        c_q = rms(_mm(x, p["w_qa"], dtype), p["q_norm"], eps)
        q = _mm(c_q, p["w_qb"], dtype).reshape(r, l, h, nope + rope)
        a = _mm(x, p["w_kva"], dtype)
        kv = _mm(rms(a[..., : cfg.kv_lora_rank], p["kv_norm"], eps), p["w_kvb"], dtype)
        kv = kv.reshape(r, l, h, nope + dv)
        q = _rope(jnp.concatenate([q[..., nope:], q[..., :nope]], -1), l, rope, cfg.rope_theta)
        k_pe = _rope(a[..., None, cfg.kv_lora_rank:], l, rope, cfg.rope_theta)          # [R, L, 1, rope]
        k = jnp.concatenate([jnp.broadcast_to(k_pe, (r, l, h, rope)), kv[..., :nope]], -1)
        v = kv[..., nope:]
    with jax.named_scope("stream/attn/core"):
        # [R, L, H, d] -> [R, H, 1, L, d]: a key head for each query head.
        heads = lambda t: jnp.transpose(t, (0, 2, 1, 3))
        o = segment_attention(heads(q)[:, :, None], heads(k), heads(v), seg, cfg.attn_block, (nope + rope) ** -0.5, kind.window)
        o = jnp.transpose(o[:, :, 0], (0, 2, 1, 3)).reshape(r, l, h * dv)
    with jax.named_scope("stream/attn/proj"):
        return _mm(o, p["w_o"], dtype)


# -- the expert layer -----------------------------------------------------------------


_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu, "relu2": lambda x: jnp.square(jax.nn.relu(x))}
# Activations whose expert has no gate: down(act(up x)), as the sources
# that name them have it.
UNGATED = ("relu2",)


def expert_matrices(act: str) -> Tuple[str, ...]:
    """The names of an expert's matrices, the input side's first and the
    down matrix last: the gate only where ``act`` has one."""
    return ("w_up", "w_down") if act in UNGATED else ("w_gate", "w_up", "w_down")


def _hidden(act: str):
    """The expert's hidden row from its input side's products: act(g) * u,
    or act(u) where it has no gate."""
    return _ACTS[act] if act in UNGATED else (lambda g, u: _ACTS[act](g) * u)


def _grouped(a, w, sizes, form: str = grouped_matmul.ROWS):
    """``a``'s rows by ``sizes`` through their groups of ``w`` (the
    ``form`` product of ``ops/grouped_matmul.py``: its kernel on a TPU
    where ``grouped_carrier`` says so, ``jax.lax.ragged_dot`` elsewhere),
    float32 out, under the scope ``grouped`` that names the products alone
    inside ``stream/moe/experts``."""
    with jax.named_scope("grouped"):
        n = w.shape[2] if form == grouped_matmul.ROWS else w.shape[1]
        carrier = grouped_matmul.grouped_carrier(form, a.shape[1], n, sizes.shape[0], (a.dtype, w.dtype))
        return grouped_matmul.grouped(a, w, sizes, form, carrier)


def _expert_block(xb, wb, sizes, weights, dtype, act: str):
    """One block of sorted slots through their experts: rows of ``xb`` in
    expert order, ``sizes`` rows for each expert held (``_block_plan``
    gives the last the block's rows past the held slots, at weight nought).
    ``weights``: the matrices ``expert_matrices(act)`` names."""
    dot = lambda a, w: _grouped(a, w, sizes)
    if act in UNGATED:
        w_up, w_down = weights
        h = _ACTS[act](dot(xb, w_up)).astype(dtype)
    else:
        w_gate, w_up, w_down = weights
        h = (_ACTS[act](dot(xb, w_gate)) * dot(xb, w_up)).astype(dtype)
    return dot(h, w_down) * wb[:, None]


def _transposed(weights):
    """(``W_down^T`` [E, D, F], the input side's ``[W_gate | W_up]^T`` [E,
    2F, D], or ``W_up^T`` [E, F, D] ungated): the right factors of
    ``_expert_block_bwd``'s products for ``dh`` and ``dxb``."""
    *up, w_down = weights
    down_t = jnp.swapaxes(w_down, 1, 2)
    return down_t, jnp.swapaxes(jnp.concatenate(up, axis=2) if len(up) > 1 else up[0], 1, 2)


def _expert_block_bwd(xb, wb, sizes, up, w_down_t, w_up_t, dyb, dtype, act: str):
    """``jax.vjp(_expert_block)``'s gradients, in the dtypes it gives
    them, from seven grouped products where autodiff runs nine (five for
    six where the expert has no gate): ``(dxb, dwb, dW_gate, dW_up,
    dW_down)``, ``dW_gate`` absent ungated, for the cotangent ``dyb`` [T, D]
    (float32, or the bfloat16 it was gathered in: its widening is exact).
    ``up``: the input side's matrices, ``(W_gate, W_up)`` or ``(W_up,)``.

    ``_expert_block`` ends in ``(h . W_down) * wb``, so autodiff forms
    ``dwb = sum_D(dyb * (h . W_down))`` and has to run the down product's
    forward again to have its left factor.  But
    ``sum_D(dyb * (h . W_down)) = sum_F(h * (dyb . W_down^T))``, and
    ``dyb . W_down^T`` is the ``dh`` the backward needs anyway, before its
    multiply by ``wb``: so the down product is absent from this backward,
    ``dwb`` is a row sum over the F columns of ``h`` and not over the D of
    the output, and ``dh`` is made once.  ``dxb`` is one product over the
    2F columns of ``[dG | dU]`` in place of two products over F and their
    sum.  ``w_down_t`` [E, D, F] and ``w_up_t`` [E, 2F, D] (ungated [E,
    F, D]) are ``W_down`` and ``[W_gate | W_up]`` with their last two axes
    exchanged (``_transposed`` makes them, ``_routed_bwd`` once a layer).
    ``_expert_block`` stays the oracle this is held to
    (tests/test_stream_ranker.py, tests/test_nemotron_reference.py)."""
    dot = lambda a, w: _grouped(a, w, sizes)
    by_group = lambda a, b: _grouped(a, b, sizes, grouped_matmul.BY_GROUP).astype(dtype)
    # The activation's derivative is autodiff's, of the elementwise
    # expression alone: _ACTS stays the one place an activation is named.
    h32, pull = jax.vjp(_hidden(act), *(dot(xb, w) for w in up))
    h = h32.astype(dtype)
    dh = dot(dyb, w_down_t)
    dwb = jnp.sum(h.astype(F32) * dh, axis=1)
    # The cotangent of ``h`` in h's dtype, as autodiff hands it on.  The
    # float32 factors go to their products in ``dtype``: the one pass the
    # chip's ragged_dot makes of a float32 factor against a bfloat16 one
    # (bit for bit, PERF.md section 6), written half as wide.
    d_up = tuple(g.astype(dtype) for g in pull((dh * wb[:, None]).astype(dtype).astype(F32)))
    d_side = jnp.concatenate(d_up, axis=1) if len(d_up) > 1 else d_up[0]
    dxb = dot(d_side, w_up_t).astype(xb.dtype)
    dyw = (dyb * wb[:, None]).astype(dtype)
    return (dxb, dwb, *(by_group(xb, g) for g in d_up), by_group(h, dyw))


def _block_plan(i, block: int, ends, tok_sorted, w_sorted):
    """Block ``i`` of the sorted slots: (its slots' tokens, their weights,
    each expert's rows, which rows are a held expert's).  The rows past the
    held slots go through with the last expert's group as rows of their
    own number at weight nought, so that a block is the same work to the
    gather, the grouped product and the scatter-add whatever share of it
    the routing filled."""
    lo = i * block
    edges = jnp.clip(jnp.concatenate([jnp.zeros((1,), ends.dtype), ends]) - lo, 0, block)
    at = jnp.arange(block, dtype=ends.dtype)
    valid = at + lo < ends[-1]
    per = (edges[1:] - edges[:-1]).at[-1].add(block - edges[-1])
    rows = jnp.where(valid, jax.lax.dynamic_slice(tok_sorted, (lo,), (block,)), at)
    wb = jnp.where(valid, jax.lax.dynamic_slice(w_sorted, (lo,), (block,)), 0.0)
    return rows, wb, per, valid


def _blocks_left(blocks: int, block: int, ends):
    """The loop's condition: ``blocks`` blocks whatever the routing, and as
    many more as the held slots fill."""
    return lambda carry: (carry[0] < blocks) | (carry[0] * block < ends[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def routed_experts(x, w_sorted, tok_sorted, sizes, weights, dtype, blocks, act="silu"):
    """sum over the held experts' slots of w . down(act(gate x) * up x), or
    of w . down(act(up x)) where ``act`` has no gate; ``weights`` the
    held experts' matrices ``expert_matrices(act)`` names.

    x [T, D]; ``tok_sorted`` [T*k] the token of every slot, the held
    experts' slots first and in expert order; ``w_sorted`` their weights;
    ``sizes`` [count] the slots each held expert received.  The slots go
    through in blocks of T: ``blocks`` of them always, so that the layer's
    time does not move with the routing while the held slots are under
    ``blocks`` tenths of all slots, and as many more as they fill (k when
    every slot is held): nothing is dropped.

    The backward (``_routed_bwd``) runs the same blocks and takes each
    block's five gradients from ``_expert_block_bwd``, written by hand
    and held to ``jax.vjp(_expert_block)``, the oracle: the slot weights'
    gradient by ``sum_D(dy * (h . W_down)) = sum_F(h * (dy . W_down^T))``,
    whose right side is a row sum over F of the ``dh`` the backward makes
    anyway, so no block runs the down product a second time."""
    return _routed_fwd(x, w_sorted, tok_sorted, sizes, weights, dtype, blocks, act)[0]


def _routed_fwd(x, w_sorted, tok_sorted, sizes, matrices, dtype, blocks, act):
    block = x.shape[0]
    with jax.named_scope("stream/moe/experts"):
        weights = tuple(w.astype(dtype) for w in matrices)
    # A block's rows go to expert order and back by ops/slot_rows.py: its
    # kernels on a TPU, jnp.take and .at[].add elsewhere, in the form
    # (``pack``) the carrier moves.
    mover = slot_rows.row_mover(x.shape[1], x.dtype)
    with jax.named_scope("stream/moe/dispatch"):
        ends = jnp.cumsum(sizes)
        xp = slot_rows.pack(x, mover)

    def body(carry):
        i, yp = carry
        rows, wb, per, valid = _block_plan(i, block, ends, tok_sorted, w_sorted)
        with jax.named_scope("stream/moe/dispatch"):
            xb = slot_rows.gather_packed(xp, rows, x.dtype, mover)
        with jax.named_scope("stream/moe/experts"):
            ob = _expert_block(xb, wb, per, weights, dtype, act)
        with jax.named_scope("stream/moe/combine"):
            yp = slot_rows.add_packed(yp, rows, jnp.where(valid[:, None], ob, 0.0), mover)
        return i + 1, yp

    # The loop's own work (its count, each block's plan, its carry) is
    # named by a scope no reader sums; each block's by the layer's.
    with jax.named_scope("stream/expert_blocks"):
        _, yp = jax.lax.while_loop(
            _blocks_left(blocks, block, ends), body,
            (jnp.zeros((), ends.dtype), slot_rows.pack(jnp.zeros(x.shape, F32), mover)),
        )
    with jax.named_scope("stream/moe/combine"):
        y = slot_rows.unpack(yp, mover)
    return y.astype(x.dtype), (x, w_sorted, tok_sorted, sizes, matrices)


def _routed_bwd(dtype, blocks, act, res, dy):
    x, w_sorted, tok_sorted, sizes, matrices = res
    block = x.shape[0]
    with jax.named_scope("stream/moe/experts"):
        weights = tuple(w.astype(dtype) for w in matrices)
        transposed = _transposed(weights)
    mover = slot_rows.row_mover(x.shape[1], x.dtype)
    with jax.named_scope("stream/moe/dispatch"):
        ends = jnp.cumsum(sizes)
        xp, dyp = slot_rows.pack(x, mover), slot_rows.pack(dy, mover)

    def body(carry):
        i, dxp, dw, dws = carry
        rows, wb, per, valid = _block_plan(i, block, ends, tok_sorted, w_sorted)
        with jax.named_scope("stream/moe/dispatch"):
            xb = slot_rows.gather_packed(xp, rows, x.dtype, mover)
            dyb = slot_rows.gather_packed(dyp, rows, dy.dtype, mover)
            # Masked in the dtype it was gathered in: the mask is exact
            # there, so the ``dh`` product takes it as it is.
            dyb = jnp.where(valid[:, None], dyb, jnp.zeros_like(dyb))
        with jax.named_scope("stream/moe/experts"):
            dxb, dwb, *dwe = _expert_block_bwd(
                xb, wb, per, weights[:-1], *transposed, dyb, dtype, act
            )
            dws = tuple(a + b.astype(F32) for a, b in zip(dws, dwe))
        with jax.named_scope("stream/moe/combine"):
            dxp = slot_rows.add_packed(
                dxp, rows, jnp.where(valid[:, None], dxb.astype(F32), 0.0), mover
            )
            dw = jax.lax.dynamic_update_slice(dw, jnp.where(valid, dwb, 0.0), (i * block,))
        return i + 1, dxp, dw, dws

    with jax.named_scope("stream/expert_blocks"):
        _, dxp, dw, dws = jax.lax.while_loop(
            _blocks_left(blocks, block, ends), body,
            (
                jnp.zeros((), ends.dtype), slot_rows.pack(jnp.zeros(x.shape, F32), mover),
                jnp.zeros(w_sorted.shape, F32),
                tuple(jnp.zeros(w.shape, F32) for w in matrices),
            ),
        )
    with jax.named_scope("stream/moe/combine"):
        dx = slot_rows.unpack(dxp, mover)
    return (dx.astype(x.dtype), dw, None, None, dws)


routed_experts.defvjp(_routed_fwd, _routed_bwd)


def router_logits(p, x):
    """x [..., D] -> [..., experts], float32."""
    return jnp.dot(x.astype(F32), p["router"], precision=jax.lax.Precision.HIGHEST)


def route(p, x, cfg: StreamRankerConfig, logits=None):
    """x [T, D] -> (weights [T, k], experts [T, k]) over all the experts.
    ``logits`` [T, experts] where the router has read another input than
    the experts'; left out, it reads ``x``.  Where the layer holds a
    selection bias (``p["bias"]``) the experts are chosen by score + bias
    and weighted by the score."""
    k = cfg.num_experts_per_tok
    with jax.named_scope("stream/moe/router"):
        if logits is None:
            logits = router_logits(p, x)
        if cfg.scoring_func == "sigmoid":
            probs = jax.nn.sigmoid(logits)
        elif not cfg.softmax_after_topk:
            probs = jax.nn.softmax(logits, axis=-1)
    with jax.named_scope("stream/moe/dispatch"):
        if cfg.softmax_after_topk:
            # Softmax over the k taken sums to one: norm_topk_prob leaves it.
            top_l, top_i = jax.lax.top_k(logits, k)
            top_w = jax.nn.softmax(top_l, axis=-1)
        else:
            if "bias" in p:
                _, top_i = jax.lax.top_k(probs + jax.lax.stop_gradient(p["bias"]), k)
                top_w = jnp.take_along_axis(probs, top_i, axis=-1)
            else:
                top_w, top_i = jax.lax.top_k(probs, k)
            if cfg.norm_topk_prob:
                top_w = top_w / top_w.sum(-1, keepdims=True)
        if cfg.routed_scaling_factor != 1.0:
            top_w = top_w * cfg.routed_scaling_factor
    return top_w, top_i


def _swiglu(p, x, dtype):
    """down(silu(gate x) * up x), the product in float32."""
    h = jax.nn.silu(_mm(x, p["w_gate"], dtype).astype(F32)) * _mm(x, p["w_up"], dtype).astype(F32)
    return _mm(h, p["w_down"], dtype)


@functools.partial(jax.checkpoint, static_argnums=(2, 3))
def _ungated_mlp(p, x, dtype, act: str):
    """down(act(up x)), the activation in float32; recomputed in its own
    backward, so that its float32 rows ([T, F] at the shared expert's
    width) are not held through the routed experts' backward."""
    return _mm(_ACTS[act](_mm(x, p["w_up"], dtype).astype(F32)), p["w_down"], dtype)


def dense_mlp(p, x, cfg: StreamRankerConfig):
    """x [T, D] -> [T, D]: a leading dense layer's SwiGLU of
    ``intermediate_size``."""
    with jax.named_scope("stream/mlp"):
        return _swiglu(p, x, cfg.dtype)


def expert_layer(p, x, cfg: StreamRankerConfig, logits=None):
    """x [T, D] -> (y [T, D], token-slots each held expert received
    [count], the token-slots routed to each of all the experts [experts]
    where the layer holds a selection bias, else None).  ``logits`` as
    ``route`` takes them."""
    dtype = cfg.dtype
    k = cfg.num_experts_per_tok
    first, count = cfg.experts_held
    top_w, top_i = route(p, x, cfg, logits)
    loads = None
    if "bias" in p:
        with jax.named_scope("stream/moe/router"):
            loads = jnp.bincount(top_i.reshape(-1), length=cfg.num_experts).astype(jnp.uint32)
    with jax.named_scope("stream/moe/dispatch"):
        # A held expert's slots sort by expert, every absent one's after.
        local = top_i - first
        key = jnp.where((local >= 0) & (local < count), local, count).reshape(-1)
        order = jnp.argsort(key)
        sizes = jnp.diff(
            jnp.searchsorted(key[order], jnp.arange(count + 1, dtype=key.dtype))
        ).astype(jnp.int32)
        tok_sorted = (order // k).astype(jnp.int32)
        w_sorted = top_w.reshape(-1)[order]
    routed = routed_experts(
        x, w_sorted, tok_sorted, sizes, tuple(p[name] for name in expert_matrices(cfg.hidden_act)),
        dtype, min(cfg.expert_blocks, k), cfg.hidden_act,
    )
    if not cfg.shared_expert_intermediate_size:
        return routed, sizes, loads
    with jax.named_scope("stream/moe/shared"):
        if cfg.hidden_act in UNGATED:
            shared = _ungated_mlp(p["shared"], x, dtype, cfg.hidden_act)
        else:
            shared = _swiglu(p["shared"], x, dtype)
        gate = jax.nn.sigmoid(jnp.dot(x.astype(F32), p["shared_gate"])) if cfg.shared_expert_gate else None
    with jax.named_scope("stream/moe/combine"):
        y = routed.astype(F32) + (shared.astype(F32) if gate is None else gate * shared.astype(F32))
    return y.astype(dtype), sizes, loads


# -- the decoder ------------------------------------------------------------------------


def _row_by_row(fn, p, x, *sides):
    """``fn(p, x, *sides)`` over [R, L, ...] arrays one row after another,
    each row recomputed in its own backward: a mixer's temporaries are
    one row's.  (On the v5e at the cell's size one row at a time was also
    the fastest: 2.36 s a dispatch against 2.52 for two and 2.63 for four;
    my chip run, PR 27.)"""
    one = lambda out: jax.tree_util.tree_map(lambda a: a[0], out)
    row = lambda rows: one(jax.checkpoint(fn)(p, *(a[None] for a in rows)))
    # A row's slices in and writes out, and the loop's count: a scope no
    # reader sums (the mixer's own scopes name what a row computes).
    with jax.named_scope("stream/rows"):
        return jax.lax.map(row, (x, *sides))


# The scope of a mixer's first norm: its projections'.
_MIXER_PROJ = {ATTENTION: "stream/attn/proj", DELTANET: "stream/gdn/proj", MAMBA: "stream/mamba/proj"}


def _block(p, x, start, seg, pos, cfg: StreamRankerConfig, kind: Mixer, feed: str = EXPERTS):
    """h = x + mixer(rms(x)); out = h + moe(rms(h)), or h + mlp(rms(h))
    where the layer's feed-forward is ``DENSE``; a part the layer has none
    of (``NO_MIXER``, ``NO_FEED_FORWARD``) is left out, its residual with
    it.  Both halves are recomputed in their backward: what a block keeps
    is its input, h and, where the router reads the first norm, its logits
    [R, L, experts] (made in the first half, planned from in the second).
    Returns (out, token-slots each held expert received, the slots routed
    to each of all the experts where the layer holds a selection bias), the
    last two None where they are not."""
    r, l, d = x.shape

    def mixer(p, x, start, seg, pos):
        with jax.named_scope(_MIXER_PROJ[kind.kind]):
            h = rms(x, p["norm1"], cfg.rms_norm_eps)
        logits = None
        if cfg.router_before_attention:
            with jax.named_scope("stream/moe/router"):
                logits = router_logits(p["moe"], h)
        if kind.kind == ATTENTION:
            attend = latent_attention if cfg.kv_lora_rank else gated_attention
            return attend(p["attn"], h, seg, cfg, kind), logits
        if kind.kind == MAMBA:
            return mamba_mixer(p["mamba"], h, start, seg, pos, cfg), logits
        return gated_delta_net(p["gdn"], h, start, seg, pos, cfg), logits

    @jax.checkpoint
    def experts(p, x, logits):
        with jax.named_scope("stream/moe/router"):
            h = rms(x, p["norm2"], cfg.rms_norm_eps).reshape(r * l, d)
        if logits is not None:
            logits = logits.reshape(r * l, -1)
        y, sizes, loads = expert_layer(p["moe"], h, cfg, logits)
        return y.reshape(r, l, d), sizes, loads

    @jax.checkpoint
    def mlp(p, x, logits):
        with jax.named_scope("stream/mlp"):
            h = rms(x, p["norm2"], cfg.rms_norm_eps).reshape(r * l, d)
        return dense_mlp(p["mlp"], h, cfg).reshape(r, l, d), None, None

    logits = None
    if kind.kind != NO_MIXER:
        y, logits = _row_by_row(mixer, p, x, start, seg, pos)
        with jax.named_scope("stream/residual"):
            x = x + y
    if feed == NO_FEED_FORWARD:
        return x, None, None
    # The call's own work (the cotangent it adds into the residual's) is
    # named as the row loop's is; the layer's scopes name its insides.
    with jax.named_scope("stream/expert_layer"):
        y, sizes, loads = (mlp if feed == DENSE else experts)(p, x, logits)
    with jax.named_scope("stream/residual"):
        return x + y, sizes, loads


def forward(params, cfg: StreamRankerConfig, hop_feats, src, dst, qef):
    """(pred [B], token-slots of each held expert by expert layer [expert
    layers, count], the attention layers' ``ATTENTION_COUNTS`` by name:
    keys attended and keys in the band, block pairs run and block pairs in
    the band, each [window layers, full layers]; the slots routed to each
    of all the experts by expert layer [expert layers, experts] where the
    layers hold a selection bias, else None)."""
    l = cfg.positions
    if src.shape[0] % l:
        raise ValueError(f"a batch of {src.shape[0]} records is not rows of {l} positions")
    r = src.shape[0] // l
    start, seg, pos = segments(dst, l)
    with jax.named_scope("stream/embed"):
        feats, prev = standard_inputs(hop_feats, src, dst, qef[:, 0], start.reshape(-1), cfg)
        x = jnp.take(params["embed"]["embedding"], src, axis=0).astype(cfg.dtype)
        x = x + _mm(jnp.concatenate([feats, prev[:, None]], -1), params["w_in"], cfg.dtype)
        x = x.reshape(r, l, cfg.hidden_size)
    sizes, loads = [], []
    counts = {kind: jnp.zeros((len(ATTENTION_COUNTS),), jnp.uint32) for kind in ATTENTION_KINDS}
    for i, (kind, feed) in enumerate(zip(layer_kinds(cfg), feed_forwards(cfg))):
        x, n, load = _block(params[f"layer_{i}"], x, start, seg, pos, cfg, kind, feed)
        if n is not None:
            sizes.append(n)
        if load is not None:
            loads.append(load)
        if kind.kind == ATTENTION:
            with jax.named_scope("stream/attn/count"):
                counts[kind.attention_kind] += jnp.stack(
                    attention_keys(pos, kind.window) + attention_pairs(seg, cfg.attn_block, kind.window)
                )
    with jax.named_scope("stream/head"):
        h = rms(x, params["final_norm"], cfg.rms_norm_eps).astype(F32)
        # The history up to the previous transfer scores every host as the
        # next parent; the record's own parent is read off.
        column = jnp.take(params["head"], src, axis=0).reshape(r, l, -1)
        warm = jnp.sum(_shift(h, 1) * column, axis=-1)
        cold = jnp.dot(feats, params["cold"]["kernel"])[:, 0] + params["cold"]["bias"][0]
        pred = jnp.where(start, cold.reshape(r, l), warm).reshape(-1)
    with jax.named_scope("stream/attn/count"):
        by_name = jnp.stack([counts[kind] for kind in ATTENTION_KINDS], axis=1)
        by_name = dict(zip(ATTENTION_COUNTS, by_name))
    with jax.named_scope("stream/moe/count"):
        sizes = jnp.stack(sizes)
        loads = jnp.stack(loads) if loads else None
    return pred, sizes, by_name, loads


def _normal(key, shape, dtype=F32):
    return 0.02 * jax.random.normal(key, shape, dtype)


def _a_log(key, shape, dtype=F32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-4, 16.0))


def _mamba_a_log(key, shape, dtype=F32):
    """Mamba-2's: A uniform in [1, 16]."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _conv_init(key, shape, dtype=F32):
    """A depthwise conv's taps [K, C] as PyTorch's Conv1d (and Mamba-2)
    draws them: uniform in +-1 / sqrt(K), K the fan-in of one channel."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _dt_bias(key, shape, dtype=F32, *, lo: float, hi: float, floor: float):
    """Mamba-2's: dt log-uniform in [lo, hi], at least ``floor``, and the
    bias its inverse under softplus."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, np.log(lo), np.log(hi)))
    dt = jnp.maximum(dt, floor)
    return dt + jnp.log(-jnp.expm1(-dt))


def parameter_shapes(cfg: StreamRankerConfig, hop_dim: int, n: int) -> dict:
    """name -> (initialiser, shape) of every parameter but the embedding
    (an ``nn.Embed``).  Nested names are joined with '.'."""
    d, hk, hv = cfg.hidden_size, cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    h, kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    e, f, fs = cfg.experts_held[1], cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    zeros, ones = nn.initializers.zeros, nn.initializers.ones
    out = {
        "w_in": (_normal, (2 * hop_dim + 1, d)),
        "final_norm": (zeros, (d,)),
        "head": (_normal, (n, d)),
        "cold.kernel": (_normal, (2 * hop_dim, 1)),
        "cold.bias": (zeros, (1,)),
    }
    for i, (kind, feed) in enumerate(zip(layer_kinds(cfg), feed_forwards(cfg))):
        pre = f"layer_{i}."
        if kind.kind == ATTENTION and cfg.kv_lora_rank:
            qk, rank = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.kv_lora_rank
            out.update({
                pre + "attn.w_qa": (_normal, (d, cfg.q_lora_rank)),
                pre + "attn.q_norm": (zeros, (cfg.q_lora_rank,)),
                pre + "attn.w_qb": (_normal, (cfg.q_lora_rank, h * qk)),
                pre + "attn.w_kva": (_normal, (d, rank + cfg.qk_rope_head_dim)),
                pre + "attn.kv_norm": (zeros, (rank,)),
                pre + "attn.w_kvb": (_normal, (rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim))),
                pre + "attn.w_o": (_normal, (h * cfg.v_head_dim, d)),
            })
        elif kind.kind == ATTENTION:
            out.update({
                pre + "attn.w_q": (_normal, (d, (2 if cfg.attention_gate else 1) * h * hd)),
                pre + "attn.w_k": (_normal, (d, kv * hd)),
                pre + "attn.w_v": (_normal, (d, kv * hd)),
            })
            if cfg.qk_norm:
                out.update({pre + "attn.q_norm": (zeros, (hd,)), pre + "attn.k_norm": (zeros, (hd,))})
            out[pre + "attn.w_o"] = (_normal, (h * hd, d))
        elif kind.kind == MAMBA:
            di, xbc = cfg.mamba_num_heads * cfg.mamba_head_dim, 2 * cfg.mamba_n_groups * cfg.ssm_state_size
            nh = cfg.mamba_num_heads
            dt_bias = functools.partial(
                _dt_bias, lo=cfg.time_step_min, hi=cfg.time_step_max, floor=cfg.time_step_floor
            )
            out.update({
                pre + "mamba.w_in": (_normal, (d, 2 * di + xbc + nh)),
                pre + "mamba.conv": (_conv_init, (cfg.mamba_conv_kernel, di + xbc)),
                pre + "mamba.conv_bias": (zeros, (di + xbc,)),
                pre + "mamba.dt_bias": (dt_bias, (nh,)),
                pre + "mamba.A_log": (_mamba_a_log, (nh,)),
                pre + "mamba.D": (ones, (nh,)),
                pre + "mamba.norm": (ones, (di,)),
                pre + "mamba.w_out": (_normal, (di, d)),
            })
        elif kind.kind == DELTANET:
            out.update({
                pre + "gdn.w_qkvz": (_normal, (d, 2 * hk * dk + 2 * hv * dv)),
                pre + "gdn.w_ba": (_normal, (d, 2 * hv)),
                pre + "gdn.conv": (_normal, (cfg.linear_conv_kernel_dim, 2 * hk * dk + hv * dv)),
                pre + "gdn.A_log": (_a_log, (hv,)),
                pre + "gdn.dt_bias": (ones, (hv,)),
                pre + "gdn.norm": (ones, (dv,)),
                pre + "gdn.w_o": (_normal, (hv * dv, d)),
            })
        if kind.kind != NO_MIXER:
            out[pre + "norm1"] = (zeros, (d,))
        if feed != NO_FEED_FORWARD:
            out[pre + "norm2"] = (zeros, (d,))
        if feed == DENSE:
            wide = cfg.intermediate_size
            out.update({
                pre + "mlp.w_gate": (_normal, (d, wide)),
                pre + "mlp.w_up": (_normal, (d, wide)),
                pre + "mlp.w_down": (_normal, (wide, d)),
            })
            continue
        if feed == NO_FEED_FORWARD:
            continue
        out[pre + "moe.router"] = (_normal, (d, cfg.num_experts))
        for name in expert_matrices(cfg.hidden_act):
            out[pre + "moe." + name] = (_normal, (e, f, d) if name == "w_down" else (e, d, f))
        if fs:
            for name in expert_matrices(cfg.hidden_act):
                out[pre + "moe.shared." + name] = (_normal, (fs, d) if name == "w_down" else (d, fs))
            if cfg.shared_expert_gate:
                out[pre + "moe.shared_gate"] = (_normal, (d, 1))
    return out


def nest(flat: dict) -> dict:
    """{'a.b': x} -> {'a': {'b': x}}."""
    out: dict = {}
    for name, value in flat.items():
        *path, leaf = name.split(".")
        node = out
        for part in path:
            node = node.setdefault(part, {})
        node[leaf] = value
    return out


def fold_step_counts(aux, span) -> None:
    """What the trainer's ledger does with the ``aux`` of a dispatch it
    has seen finished (models.Ranker.fold): the slots into the two
    counters, the attention layers' keys into ``trainer_attn_keys_*_total
    {kind}`` for the kinds the model has a layer of, and both, with the
    block pairs run and in the band (``attn_pairs_run_<kind>``,
    ``attn_pairs_in_band_<kind>``), onto the dispatch's span (closed at
    enqueue: the ring keeps the span itself, so a reader of the ring sees
    the attributes; an exporter that wrote the span out at its close does
    not)."""
    from ..trainer.metrics import (
        ATTN_KEYS_ATTENDED, ATTN_KEYS_IN_BAND, MOE_ROUTE_MAX_OVER_MEAN, MOE_SLOTS_HELD, MOE_SLOTS_ROUTED,
    )

    load = np.asarray(aux["expert_tokens"][-1])
    routed, held = int(aux["slots_routed"][-1]), int(load.sum())
    MOE_SLOTS_ROUTED.inc(routed)
    MOE_SLOTS_HELD.inc(held)
    span.set(
        moe_slots_routed=routed, moe_slots_held=held,
        moe_load_max=int(load.max()), moe_load_mean=float(load.mean()),
    )
    if "expert_routes" in aux:
        # Over all the experts, held here or not: the busiest layer's
        # busiest expert, and the mean (the same in every layer).
        routes = np.asarray(aux["expert_routes"][-1])
        MOE_ROUTE_MAX_OVER_MEAN.set(float(routes.max() / routes.mean()))
        span.set(moe_route_max=int(routes.max()), moe_route_mean=float(routes.mean()))
    counts = {name: np.asarray(aux[name][-1]) for name in ATTENTION_COUNTS}
    for at, kind in enumerate(ATTENTION_KINDS):
        if counts["attn_keys_in_band"][at]:
            ATTN_KEYS_ATTENDED.inc(int(counts["attn_keys_attended"][at]), kind=kind)
            ATTN_KEYS_IN_BAND.inc(int(counts["attn_keys_in_band"][at]), kind=kind)
            span.set(**{f"{name}_{kind}": int(count[at]) for name, count in counts.items()})


def _grouped_products(cfg: StreamRankerConfig):
    """(name, form, K, N) of each kind of grouped product an expert block
    runs, every factor in the activations' dtype, as ``_expert_block`` and
    ``_expert_block_bwd`` hand them to ``_grouped``: gate and up (and
    ``dh``), down, ``dxb`` over ``[dG | dU]``, the weights' gradients; an
    expert with no gate runs up, down and ``dxb`` over ``dU`` alone."""
    d, f = cfg.hidden_size, cfg.moe_intermediate_size
    rows, by_group = grouped_matmul.ROWS, grouped_matmul.BY_GROUP
    if cfg.hidden_act in UNGATED:
        return (
            ("up", rows, d, f), ("down", rows, f, d), ("dxb", rows, f, d),
            ("dW_up", by_group, d, f), ("dW_down", by_group, f, d),
        )
    return (
        ("gate_up", rows, d, f), ("down", rows, f, d), ("dxb", rows, 2 * f, d),
        ("dW_gate_up", by_group, d, f), ("dW_down", by_group, f, d),
    )


def grouped_carrier(cfg: StreamRankerConfig) -> str:
    """Which carrier runs the expert blocks' grouped products
    (``grouped_matmul.grouped_carrier`` for each kind): its name where
    every kind has the same, else ``kind=carrier`` for each, comma-joined."""
    got = [
        (name, grouped_matmul.grouped_carrier(form, k, n, cfg.experts_held[1], (cfg.dtype, cfg.dtype)))
        for name, form, k, n in _grouped_products(cfg)
    ]
    if len({c for _, c in got}) == 1:
        return got[0][1]
    return ",".join(f"{name}={c}" for name, c in got)


def carrier_attrs(cfg: StreamRankerConfig) -> dict:
    """What the ``trainer/run`` span says of this ranker's step
    (models.Ranker.run_attrs): which carrier moves the expert layers' slot
    rows here and which runs their grouped products, where a layer is a
    DeltaNet, which carries the delta rule's state over a row's chunks
    and, where a layer is attention, which runs its backward, by the tests
    ``routed_experts``, ``_grouped``, ``delta_rule_chunked`` and
    ``segment_attention`` themselves make."""
    kinds = [kind.kind for kind in layer_kinds(cfg)] + list(feed_forwards(cfg))
    attrs = {
        "moe_row_mover": slot_rows.row_mover(cfg.hidden_size, cfg.dtype),
        "moe_grouped_carrier": grouped_carrier(cfg),
        # How many layers have each kind of part, mixers and feed-forwards.
        **{f"layers_{k}": kinds.count(k) for k in sorted(set(kinds) - {NO_MIXER, NO_FEED_FORWARD})},
    }
    if any(kind.kind == ATTENTION for kind in layer_kinds(cfg)):
        # A latent layer's heads are each their own key head (group 1).
        latent = bool(cfg.kv_lora_rank)
        attrs["attn_carrier"] = attention_bwd.carrier(
            cfg.dtype,
            cfg.qk_nope_head_dim + cfg.qk_rope_head_dim if latent else cfg.head_dim,
            1 if latent else cfg.num_attention_heads // cfg.num_key_value_heads,
            min(cfg.attn_block, cfg.positions), cfg.positions,
        )
    if any(kind.kind == DELTANET for kind in layer_kinds(cfg)):
        attrs["gdn_scan_carrier"] = delta_scan.scan_carrier(
            cfg.dtype, cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            min(cfg.chunk, cfg.positions),
        )
    if any(kind.kind == MAMBA for kind in layer_kinds(cfg)):
        attrs["ssm_scan_carrier"] = delta_scan.scan_carrier(
            cfg.dtype, cfg.ssm_state_size, cfg.mamba_head_dim, min(cfg.chunk, cfg.positions),
        )
    return attrs


class StreamRanker(nn.Module):
    """__call__(hop_feats, table, src, dst, qef) -> [B] predicted
    log-bandwidth of each record's parent -> child transfer.  ``table`` is
    not read (the aggregation is in ``hop_feats``); ``qef`` is
    ``previous_target``'s [B, 1], zeros if left out."""

    config: StreamRankerConfig

    @nn.compact
    def __call__(
        self, hop_feats, table, src, dst, query_edge_feats=None, *, train: bool = False,
    ) -> jax.Array:
        cfg = self.config
        n, hop_dim = hop_feats.shape
        embed = nn.Embed(
            n, cfg.hidden_size, param_dtype=F32, embedding_init=_normal, name="embed"
        )
        flat = {
            name: self.param(name, init, shape)
            for name, (init, shape) in parameter_shapes(cfg, hop_dim, n).items()
        }
        layers, count = len(expert_layers(cfg)), cfg.experts_held[1]
        # Each expert layer's selection bias: state the step itself updates,
        # outside the parameters, so no gradient or optimizer touches it.
        biases = {
            i: self.variable(SELECTION_BIAS, f"layer_{i}", jnp.zeros, (cfg.num_experts,), F32)
            for i in (expert_layers(cfg) if cfg.selection_bias_rate else ())
        }
        if self.is_initializing():
            # Parameters depend on shapes alone: declared, and nothing run.
            embed(jnp.zeros((1,), jnp.int32))
            self.sow("aux", "expert_tokens", jnp.zeros((layers, count), jnp.uint32))
            self.sow("aux", "slots_routed", jnp.zeros((), jnp.uint32))
            for name in ATTENTION_COUNTS:
                self.sow("aux", name, jnp.zeros((len(ATTENTION_KINDS),), jnp.uint32))
            if biases:
                self.sow("aux", "expert_routes", jnp.zeros((layers, cfg.num_experts), jnp.uint32))
            return jnp.zeros(src.shape, F32)
        params = nest(flat)
        params["embed"] = {"embedding": embed.embedding}
        for i, bias in biases.items():
            params[f"layer_{i}"]["moe"]["bias"] = bias.value
        if query_edge_feats is None:
            query_edge_feats = jnp.zeros((src.shape[0], 1), F32)
        pred, sizes, counts, loads = forward(params, cfg, hop_feats, src, dst, query_edge_feats)
        with jax.named_scope("stream/moe/count"):
            sizes = sizes.astype(jnp.uint32)
        self.sow("aux", "expert_tokens", sizes)
        self.sow(
            "aux", "slots_routed",
            jnp.uint32(layers * cfg.num_experts_per_tok * src.shape[0]),
        )
        for name, count in counts.items():
            self.sow("aux", name, count)
        if biases:
            self.sow("aux", "expert_routes", loads)
            if self.is_mutable_collection(SELECTION_BIAS):
                with jax.named_scope("stream/moe/router"):
                    # The mean load over the step's own batch, here.
                    mean = cfg.num_experts_per_tok * src.shape[0] / cfg.num_experts
                    for bias, load in zip(biases.values(), loads):
                        bias.value = bias.value + cfg.selection_bias_rate * jnp.sign(mean - load.astype(F32))
        return pred
