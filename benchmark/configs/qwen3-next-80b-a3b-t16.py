"""``qwen3-next-80b-a3b-t16``: how the program is told this configuration,
the operations one train step needs, and the operations and bytes of the
routed experts' grouped product, all from the shapes."""

from __future__ import annotations

import functools


def model_config(m: dict):
    import jax.numpy as jnp

    from dragonfly2_tpu.models.stream import StreamRankerConfig

    return StreamRankerConfig(
        hidden_size=m["hidden_size"], num_hidden_layers=m["num_hidden_layers"],
        full_attention_interval=m["full_attention_interval"], rms_norm_eps=m["rms_norm_eps"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        partial_rotary_factor=m["partial_rotary_factor"], rope_theta=float(m["rope_theta"]),
        linear_num_key_heads=m["linear_num_key_heads"],
        linear_num_value_heads=m["linear_num_value_heads"],
        linear_key_head_dim=m["linear_key_head_dim"],
        linear_value_head_dim=m["linear_value_head_dim"],
        linear_conv_kernel_dim=m["linear_conv_kernel_dim"],
        num_experts=m["num_experts"], num_experts_per_tok=m["num_experts_per_tok"],
        moe_intermediate_size=m["moe_intermediate_size"],
        shared_expert_intermediate_size=m["shared_expert_intermediate_size"],
        norm_topk_prob=m["norm_topk_prob"],
        experts_held=(m["experts_held_first"], m["num_experts_held"]),
        positions=m["positions"], hops=m["hops"], dtype=jnp.dtype(m["dtype"]),
        target_center=m["target_center"], target_scale=m["target_scale"],
        expert_blocks=m["expert_blocks"],
    )


@functools.lru_cache(maxsize=None)
def _keys_attended(median: float, sigma: float, low: int, high: int, positions: int) -> float:
    """Keys a query attends, itself included, averaged over the records
    of rows packed from the stream-length law: its segment's records up to
    it.  400,000 streams drawn once from a fixed generator (not a run's
    traffic: the law's own mean)."""
    import numpy as np

    rng = np.random.default_rng(20250927)
    lengths = np.clip(np.rint(rng.lognormal(np.log(median), sigma, 400_000)), low, high).astype(np.int64)
    ends = np.cumsum(lengths)
    total = int(ends[-1]) // positions * positions
    # A segment starts where a stream does and at every row's start.
    marks = np.unique(np.concatenate([ends - lengths, np.arange(0, total, positions)]))
    n = np.diff(np.concatenate([marks[marks < total], [total]]))
    return float((n * (n + 1) // 2).sum() / n.sum())


def keys_attended(m: dict) -> float:
    s = m["stream_length"]
    return _keys_attended(s["median"], s["sigma"], s["min"], s["max"], m["positions"])


def layer_macs_per_record(m: dict, attention: bool) -> float:
    """Multiply-accumulates of one record's forward pass through one
    block: its mixer, the router, the shared expert and the routed experts
    at the expected load of the share held."""
    d = m["hidden_size"]
    if attention:
        h, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
        mixer = d * 2 * h * hd + 2 * d * kv * hd + h * hd * d
        # scores and weighted values over the keys of the segment so far
        mixer += 2 * h * hd * keys_attended(m)
    else:
        hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
        dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
        width = 2 * hk * dk + hv * dv
        mixer = d * (width + hv * dv) + d * 2 * hv + m["linear_conv_kernel_dim"] * width + hv * dv * d
        # the recurrence: S^T k, k u^T and S^T q per value head
        mixer += 3 * hv * dk * dv
    f, fs = m["moe_intermediate_size"], m["shared_expert_intermediate_size"]
    held_share = m["num_experts_held"] / m["num_experts"]
    experts = m["num_experts_per_tok"] * held_share * 3 * d * f
    return mixer + d * m["num_experts"] + 3 * d * fs + d + experts


def macs_per_record(m: dict, feat_dim: int) -> float:
    hop_dim = feat_dim * (1 + 2 * m["hops"]) + 2
    d = m["hidden_size"]
    layers = sum(
        layer_macs_per_record(m, (i + 1) % m["full_attention_interval"] == 0)
        for i in range(m["num_hidden_layers"])
    )
    # the adapter, the one head column and the cold-start head
    return (2 * hop_dim + 1) * d + layers + d + 2 * hop_dim


def step_flops(m: dict, graph: dict, batch: int) -> float:
    """Forward and backward of one step: 2 FLOP a MAC, the backward twice
    the forward; what rematerialisation recomputes is not counted, nor the
    gathers, the embedding's and the head's scatter, the sort or the
    optimizer."""
    return 3.0 * 2.0 * macs_per_record(m, graph["node_feature_dim"]) * batch


def expert_flops(m: dict, slots_held: float) -> float:
    """The routed experts' grouped products, forward and backward, for
    ``slots_held`` token-slots: gate, up and down, 2 FLOP a MAC, the
    backward twice the forward.  The same whatever implements it."""
    return 3.0 * 2.0 * 3 * m["hidden_size"] * m["moe_intermediate_size"] * slots_held


def expert_bytes(m: dict, slots_held: float, launches: float) -> float:
    """Bytes the grouped products have to move at least: per layer's
    launch the held experts' three matrices read in bfloat16 forward, read
    again backward and their float32 gradients written; per slot the
    hidden row read and written (bfloat16) forward and backward, and the
    intermediate row twice each way."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    weights = m["num_experts_held"] * 3 * d * f
    per_launch = weights * (2 + 2 + 4)
    per_slot = 2 * (2 * d * 2) + 2 * (2 * f * 2) * 2
    return per_launch * launches + per_slot * slots_held
