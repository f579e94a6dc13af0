"""``glm-4-7-flash-t8``: how the program is told this configuration, the
operations one train step needs, and the operations and bytes of the
routed experts' grouped product, of attention's core and of the latent
attention's four low-rank products, all from the shapes."""

from __future__ import annotations

import functools


def model_config(m: dict):
    import jax.numpy as jnp

    from dragonfly2_tpu.models.stream import ATTENTION, Mixer, StreamRankerConfig

    if m["topk_method"] != "noaux_tc":
        raise ValueError(f"topk_method {m['topk_method']!r}: the program routes as noaux_tc only")
    return StreamRankerConfig(
        hidden_size=m["hidden_size"], num_hidden_layers=m["num_hidden_layers"],
        layers=(Mixer(ATTENTION),) * m["num_hidden_layers"],
        rms_norm_eps=m["rms_norm_eps"], num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"], rope_theta=float(m["rope_theta"]),
        attention_gate=False, qk_norm=False,
        q_lora_rank=m["q_lora_rank"], kv_lora_rank=m["kv_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"], qk_rope_head_dim=m["qk_rope_head_dim"],
        v_head_dim=m["v_head_dim"],
        first_k_dense_replace=m["first_k_dense_replace"], intermediate_size=m["intermediate_size"],
        num_experts=m["n_routed_experts"], num_experts_per_tok=m["num_experts_per_tok"],
        moe_intermediate_size=m["moe_intermediate_size"],
        shared_expert_intermediate_size=m["n_shared_experts"] * m["moe_intermediate_size"],
        shared_expert_gate=False, norm_topk_prob=m["norm_topk_prob"], hidden_act=m["hidden_act"],
        # noaux_tc: sigmoid scores, chosen with the selection bias added.
        scoring_func="sigmoid", routed_scaling_factor=m["routed_scaling_factor"],
        selection_bias_rate=m["selection_bias_rate"],
        experts_held=(m["experts_held_first"], m["num_experts_held"]),
        positions=m["positions"], hops=m["hops"], dtype=jnp.dtype(m["dtype"]),
        target_center=m["target_center"], target_scale=m["target_scale"],
        expert_blocks=m["expert_blocks"], attn_block=m["attn_block"],
    )


@functools.lru_cache(maxsize=None)
def _keys_attended(median: float, sigma: float, low: int, high: int, positions: int) -> float:
    """Keys a query attends, itself included, averaged over the records of
    rows packed from the stream-length law: its segment's records up to it.
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
    return float((n * (n + 1) // 2).sum() / n.sum())


def keys_attended(m: dict) -> float:
    s = m["stream_length"]
    return _keys_attended(s["median"], s["sigma"], s["min"], s["max"], m["positions"])


def qk_head_dim(m: dict) -> int:
    return m["qk_nope_head_dim"] + m["qk_rope_head_dim"]


def latent_macs_per_record(m: dict) -> float:
    """The four low-rank products of one record through one layer:
    W_qa, W_qb, W_kva, W_kvb."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    q, kv = m["q_lora_rank"], m["kv_lora_rank"]
    return (
        d * q + q * h * qk_head_dim(m)
        + d * (kv + m["qk_rope_head_dim"]) + kv * h * (m["qk_nope_head_dim"] + m["v_head_dim"])
    )


def attention_macs_per_record(m: dict) -> float:
    """One record through one layer's attention: the latent products, the
    output projection, and scores and weighted values over the keys
    attended."""
    h = m["num_attention_heads"]
    core = h * (qk_head_dim(m) + m["v_head_dim"]) * keys_attended(m)
    return latent_macs_per_record(m) + h * m["v_head_dim"] * m["hidden_size"] + core


def expert_layer_macs_per_record(m: dict) -> float:
    """The router, the shared expert, and the routed experts at the
    expected load of the share held."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    held_share = m["num_experts_held"] / m["n_routed_experts"]
    routed = m["num_experts_per_tok"] * held_share * 3 * d * f
    return d * m["n_routed_experts"] + m["n_shared_experts"] * 3 * d * f + routed


def macs_per_record(m: dict, feat_dim: int) -> float:
    hop_dim = feat_dim * (1 + 2 * m["hops"]) + 2
    d, layers, dense = m["hidden_size"], m["num_hidden_layers"], m["first_k_dense_replace"]
    blocks = (
        layers * attention_macs_per_record(m)
        + dense * 3 * d * m["intermediate_size"]
        + (layers - dense) * expert_layer_macs_per_record(m)
    )
    # the adapter, the one head column and the cold-start head
    return (2 * hop_dim + 1) * d + blocks + d + 2 * hop_dim


def step_flops(m: dict, graph: dict, batch: int) -> float:
    """Forward and backward of one step: 2 FLOP a MAC, the backward twice
    the forward; what rematerialisation recomputes is not counted, nor what
    a band computes and masks away, the gathers, the embedding's and the
    head's scatter, the sort, the selection bias's rule or the optimizer."""
    return 3.0 * 2.0 * macs_per_record(m, graph["node_feature_dim"]) * batch


def attention_flops(m: dict, keys: float) -> float:
    """Attention's core for ``keys`` keys attended (summed over the
    queries, one layer's or several'): scores at the q/k head's dims and
    weighted values at v's forward, and the four products of the backward
    (dV and dP at v's, dQ and dK at q/k's), every head, 2 FLOP a MAC.  What
    recomputation repeats and what is computed and masked away are not
    counted: the same whatever implements it."""
    return 3.0 * 2.0 * m["num_attention_heads"] * (qk_head_dim(m) + m["v_head_dim"]) * keys


def latent_flops(m: dict, records: float) -> float:
    """The latent attention's four low-rank products for ``records``
    records through every layer, forward and backward (the backward twice
    the forward), 2 FLOP a MAC; their norms, RoPE and the key's broadcast
    are not counted."""
    return 3.0 * 2.0 * latent_macs_per_record(m) * m["num_hidden_layers"] * records


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
