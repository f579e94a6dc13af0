"""``nemotron-3-nano-30b-a3b-t16``: how the program is told this
configuration, the operations one train step needs, and the operations
and bytes of the Mamba-2 layers' SSD scan, all from the shapes."""

from __future__ import annotations

import functools


def model_config(m: dict):
    import jax.numpy as jnp

    from dragonfly2_tpu.models.stream import StreamRankerConfig, hybrid_layers

    if m["mlp_hidden_act"] != "relu2":
        raise ValueError(f"mlp_hidden_act {m['mlp_hidden_act']!r}: the program's ungated expert is relu2")
    if (m["n_group"], m["topk_group"]) != (1, 1):
        raise ValueError("group-limited routing: the program takes the top-k over all experts")
    mixers, feeds = hybrid_layers(m["hybrid_override_pattern"])
    if len(mixers) != m["num_hidden_layers"]:
        raise ValueError(f"{len(mixers)} letters in the pattern for {m['num_hidden_layers']} layers")
    return StreamRankerConfig(
        hidden_size=m["hidden_size"], num_hidden_layers=m["num_hidden_layers"],
        layers=mixers, feed_forwards=feeds, rms_norm_eps=m["norm_eps"],
        num_attention_heads=m["num_attention_heads"], num_key_value_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"], attention_gate=False, qk_norm=False,
        mamba_num_heads=m["mamba_num_heads"], mamba_head_dim=m["mamba_head_dim"],
        mamba_n_groups=m["n_groups"], ssm_state_size=m["ssm_state_size"], mamba_conv_kernel=m["conv_kernel"],
        time_step_min=m["time_step_min"], time_step_max=m["time_step_max"],
        time_step_floor=m["time_step_floor"],
        num_experts=m["n_routed_experts"], num_experts_per_tok=m["num_experts_per_tok"],
        moe_intermediate_size=m["moe_intermediate_size"],
        shared_expert_intermediate_size=m["n_shared_experts"] * m["moe_shared_expert_intermediate_size"],
        shared_expert_gate=False, norm_topk_prob=m["norm_topk_prob"], hidden_act=m["mlp_hidden_act"],
        scoring_func="sigmoid", routed_scaling_factor=m["routed_scaling_factor"],
        selection_bias_rate=m["selection_bias_rate"],
        experts_held=(m["experts_held_first"], m["num_experts_held"]),
        positions=m["positions"], hops=m["hops"], dtype=jnp.dtype(m["dtype"]),
        target_center=m["target_center"], target_scale=m["target_scale"],
        expert_blocks=m["expert_blocks"], attn_block=m["attn_block"], chunk=m["chunk_size"],
    )


def pattern(m: dict) -> str:
    return m["hybrid_override_pattern"]


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


def mamba_inner(m: dict) -> int:
    return m["mamba_num_heads"] * m["mamba_head_dim"]


def mamba_macs_per_record(m: dict) -> float:
    """One record through one Mamba-2 layer: the in and out projections
    and the scan's products (``ssd_macs_per_record``)."""
    d, di = m["hidden_size"], mamba_inner(m)
    w_in = d * (2 * di + 2 * m["n_groups"] * m["ssm_state_size"] + m["mamba_num_heads"])
    return w_in + di * d + ssd_macs_per_record(m)


def ssd_macs_per_record(m: dict) -> float:
    """The SSD's products for one record of one layer, chunked at the
    configuration's ``chunk_size`` C, the causal half of a chunk counted
    ((C + 1) / 2 keys a record on average): C . B^T a group (state dims),
    those scores times dt x a head (head dims), and against the state
    carried between chunks, the output's C . S and the state's update
    B^T . (dt x), a head (state x head dims each).  The same whatever
    carries the state."""
    ng, n, h, p = m["n_groups"], m["ssm_state_size"], m["mamba_num_heads"], m["mamba_head_dim"]
    keys = (m["chunk_size"] + 1) / 2
    return ng * keys * n + h * keys * p + 2 * h * n * p


def ssd_flops(m: dict, records: float) -> float:
    """The SSD scan of ``records`` records through every Mamba-2 layer,
    forward and backward (the backward twice the forward), 2 FLOP a MAC;
    its exponentials and masks not counted."""
    return 3.0 * 2.0 * ssd_macs_per_record(m) * pattern(m).count("M") * records


def ssd_bytes(m: dict, records: float) -> float:
    """Bytes the SSD scan has to move at least for ``records`` records
    through every Mamba-2 layer: forward, x, B and C read in bfloat16 and
    dt in float32, y written in float32; backward, the same read again with
    y's cotangent, and the four cotangents written in float32."""
    ng, n, h, p = m["n_groups"], m["ssm_state_size"], m["mamba_num_heads"], m["mamba_head_dim"]
    inputs = 2 * (h * p + 2 * ng * n) + 4 * h
    grads = 4 * (h * p + 2 * ng * n + h)
    per_record = (inputs + 4 * h * p) + (inputs + 4 * h * p + grads)
    return float(per_record * pattern(m).count("M") * records)


def attention_macs_per_record(m: dict) -> float:
    """One record through the attention layer: q, k, v and o, and scores
    and weighted values over the keys attended."""
    d, h, kv, hd = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    return d * (h + 2 * kv) * hd + h * hd * d + 2 * h * hd * keys_attended(m)


def attention_flops(m: dict, keys: float) -> float:
    """Attention's core for ``keys`` keys attended (summed over the
    queries, one layer's or several'): scores and weighted values forward,
    and the four products of the backward (dV, dP, dQ, dK), every head,
    2 FLOP a MAC.  What recomputation repeats and what is computed and
    masked away are not counted: the same whatever implements it."""
    return 6.0 * 2.0 * m["num_attention_heads"] * m["head_dim"] * keys


def expert_layer_macs_per_record(m: dict) -> float:
    """The router, the shared expert, and the routed experts at the
    expected load of the share held: up and down, no gate."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    held_share = m["num_experts_held"] / m["n_routed_experts"]
    routed = m["num_experts_per_tok"] * held_share * 2 * d * f
    shared = m["n_shared_experts"] * 2 * d * m["moe_shared_expert_intermediate_size"]
    return d * m["n_routed_experts"] + shared + routed


def macs_per_record(m: dict, feat_dim: int) -> float:
    hop_dim = feat_dim * (1 + 2 * m["hops"]) + 2
    d, kinds = m["hidden_size"], pattern(m)
    blocks = (
        kinds.count("M") * mamba_macs_per_record(m)
        + kinds.count("*") * attention_macs_per_record(m)
        + kinds.count("E") * expert_layer_macs_per_record(m)
    )
    # the adapter, the one head column and the cold-start head
    return (2 * hop_dim + 1) * d + blocks + d + 2 * hop_dim


def step_flops(m: dict, graph: dict, batch: int) -> float:
    """Forward and backward of one step: 2 FLOP a MAC, the backward twice
    the forward; what rematerialisation recomputes is not counted, nor what
    a band or a chunk computes and masks away, the gathers, the conv, the
    norms, the embedding's and the head's scatter, the sort, the selection
    bias's rule or the optimizer."""
    return 3.0 * 2.0 * macs_per_record(m, graph["node_feature_dim"]) * batch

