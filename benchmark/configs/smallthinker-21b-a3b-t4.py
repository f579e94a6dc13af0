"""``smallthinker-21b-a3b-t4``: how the program is told this configuration,
the operations one train step needs, and the operations and bytes of the
routed experts' grouped product and of attention's core, all from the
shapes."""

from __future__ import annotations

import functools


def model_config(m: dict):
    import jax.numpy as jnp

    from dragonfly2_tpu.models.stream import ATTENTION, Mixer, StreamRankerConfig

    layers = tuple(
        Mixer(ATTENTION, window=m["sliding_window_size"] if windowed else 0, rope=bool(rope))
        for windowed, rope in zip(m["sliding_window_layout"], m["rope_layout"])
    )
    return StreamRankerConfig(
        hidden_size=m["hidden_size"], num_hidden_layers=m["num_hidden_layers"], layers=layers,
        rms_norm_eps=m["rms_norm_eps"], num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        partial_rotary_factor=1.0, rope_theta=float(m["rope_theta"]),
        attention_gate=False, qk_norm=False,
        num_experts=m["moe_num_primary_experts"],
        num_experts_per_tok=m["moe_num_active_primary_experts"],
        moe_intermediate_size=m["moe_ffn_hidden_size"], shared_expert_intermediate_size=0,
        norm_topk_prob=m["norm_topk_prob"], hidden_act="relu",
        # The layer's own order (the file's "layer": the six largest logits,
        # then softmax over those six), not a key of the source: its
        # moe_primary_router_apply_softmax says softmax and not another
        # squashing, whichever comes first.
        softmax_after_topk=True, router_before_attention=True,
        experts_held=(m["experts_held_first"], m["num_experts_held"]),
        positions=m["positions"], hops=m["hops"], dtype=jnp.dtype(m["dtype"]),
        target_center=m["target_center"], target_scale=m["target_scale"],
        expert_blocks=m["expert_blocks"], attn_block=m["attn_block"],
    )


@functools.lru_cache(maxsize=None)
def _keys_attended(median: float, sigma: float, low: int, high: int, positions: int, window: int) -> float:
    """Keys a query attends, itself included, averaged over the records
    of rows packed from the stream-length law: its segment's records up to
    it, the last ``window`` of them where there is a window (0: none).
    400,000 streams drawn once from a fixed generator (not a run's traffic:
    the law's own mean)."""
    import numpy as np

    rng = np.random.default_rng(20250927)
    lengths = np.clip(np.rint(rng.lognormal(np.log(median), sigma, 400_000)), low, high).astype(np.int64)
    ends = np.cumsum(lengths)
    total = int(ends[-1]) // positions * positions
    # A segment starts where a stream does and at every row's start.
    marks = np.unique(np.concatenate([ends - lengths, np.arange(0, total, positions)]))
    n = np.diff(np.concatenate([marks[marks < total], [total]]))
    w = np.minimum(n, window) if window else n
    # the first w queries see 1..w keys, the segment's other n - w see w each
    return float((w * (w + 1) // 2 + (n - w) * w).sum() / n.sum())


def keys_attended(m: dict, windowed: bool) -> float:
    s = m["stream_length"]
    return _keys_attended(
        s["median"], s["sigma"], s["min"], s["max"], m["positions"],
        m["sliding_window_size"] if windowed else 0,
    )


def layer_macs_per_record(m: dict, windowed: bool) -> float:
    """Multiply-accumulates of one record's forward pass through one
    block: the four projections, scores and weighted values over the keys
    attended, the router, and the routed experts at the expected load of
    the share held."""
    d = m["hidden_size"]
    h, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    mixer = 2 * d * h * hd + 2 * d * kv * hd + 2 * h * hd * keys_attended(m, windowed)
    held_share = m["num_experts_held"] / m["moe_num_primary_experts"]
    experts = m["moe_num_active_primary_experts"] * held_share * 3 * d * m["moe_ffn_hidden_size"]
    return mixer + d * m["moe_num_primary_experts"] + experts


def macs_per_record(m: dict, feat_dim: int) -> float:
    hop_dim = feat_dim * (1 + 2 * m["hops"]) + 2
    d = m["hidden_size"]
    layers = sum(
        layer_macs_per_record(m, bool(windowed))
        for windowed in m["sliding_window_layout"][: m["num_hidden_layers"]]
    )
    # the adapter, the one head column and the cold-start head
    return (2 * hop_dim + 1) * d + layers + d + 2 * hop_dim


def step_flops(m: dict, graph: dict, batch: int) -> float:
    """Forward and backward of one step: 2 FLOP a MAC, the backward twice
    the forward; what rematerialisation recomputes is not counted, nor what
    a band run by position computes and masks away, the gathers, the
    embedding's and the head's scatter, the sort or the optimizer."""
    return 3.0 * 2.0 * macs_per_record(m, graph["node_feature_dim"]) * batch


def attention_flops(m: dict, keys: float) -> float:
    """Attention's core for ``keys`` keys attended (summed over the
    queries, one layer's or several'): scores and weighted values forward,
    and the four products of the backward (dV, dP, dQ, dK), every head,
    2 FLOP a MAC.  What recomputation repeats and what is computed and
    masked away are not counted: the same whatever implements it."""
    return 6.0 * 2.0 * m["num_attention_heads"] * m["head_dim"] * keys


def expert_flops(m: dict, slots_held: float) -> float:
    """The routed experts' grouped products, forward and backward, for
    ``slots_held`` token-slots: gate, up and down, 2 FLOP a MAC, the
    backward twice the forward.  The same whatever implements it."""
    return 3.0 * 2.0 * 3 * m["hidden_size"] * m["moe_ffn_hidden_size"] * slots_held


def expert_bytes(m: dict, slots_held: float, launches: float) -> float:
    """Bytes the grouped products have to move at least: per layer's
    launch the held experts' three matrices read in bfloat16 forward, read
    again backward and their float32 gradients written; per slot the
    hidden row read and written (bfloat16) forward and backward, and the
    intermediate row twice each way."""
    d, f = m["hidden_size"], m["moe_ffn_hidden_size"]
    weights = m["num_experts_held"] * 3 * d * f
    per_launch = weights * (2 + 2 + 4)
    per_slot = 2 * (2 * d * 2) + 2 * (2 * f * 2) * 2
    return per_launch * launches + per_slot * slots_held
