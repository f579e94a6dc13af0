"""The GLM-4.7-Flash configuration's tiny sizes and the fixtures that
``tests/test_glm_reference.py`` uses: every width cut; the leading dense
layer and four expert layers; a q/k head of a nope and a rope part whose
sum is v's; a quarter of the experts held, four of sixteen a token."""

import pytest

from benchmark import run as bench
from tests._stream_sizes import HOP_DIM, L, N, ROWS, _records, hop  # noqa: F401 — re-exported

NAME = "glm-4-7-flash-t8"
B = ROWS * L

M = dict(
    hidden_size=32, rms_norm_eps=1e-5, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=12, kv_lora_rank=8, qk_nope_head_dim=6, qk_rope_head_dim=4, v_head_dim=10,
    rope_theta=1e6, intermediate_size=40, first_k_dense_replace=1, n_routed_experts=16,
    num_experts_per_tok=4, moe_intermediate_size=12, n_shared_experts=1, norm_topk_prob=True,
    routed_scaling_factor=1.8, topk_method="noaux_tc", hidden_act="silu", selection_bias_rate=0.001,
    num_hidden_layers=5, experts_held_first=4, num_experts_held=4, positions=L, hops=2,
    target_center=15.0, target_scale=1.0, expert_blocks=2, attn_block=8, dtype="float32",
)


@pytest.fixture(scope="module")
def ref():
    return bench.load_module("reference", NAME)


@pytest.fixture(scope="module")
def cfg():
    return bench.load_module("configs", NAME).model_config(M)
