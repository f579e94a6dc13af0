"""The Nemotron-3-Nano configuration's tiny sizes and the fixtures that
``tests/test_nemotron_reference.py`` uses: every width cut, the nine
layers ``MEMEM*EME`` kept; sixteen query heads a key head, eight Mamba-2
heads a group of B and C, a chunk of 8 that rows of 32 cross three times;
a quarter of the experts held, six of sixteen a token."""

import pytest

from benchmark import run as bench
from tests._stream_sizes import HOP_DIM, L, N, ROWS, _records, hop  # noqa: F401 — re-exported

NAME = "nemotron-3-nano-30b-a3b-t16"
B = ROWS * L

M = dict(
    hidden_size=32, norm_eps=1e-5, num_attention_heads=16, num_key_value_heads=1, head_dim=4,
    mamba_num_heads=16, mamba_head_dim=4, n_groups=2, ssm_state_size=8, conv_kernel=4, chunk_size=8,
    time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
    n_routed_experts=16, num_experts_per_tok=6, moe_intermediate_size=12,
    moe_shared_expert_intermediate_size=24, n_shared_experts=1, norm_topk_prob=True,
    routed_scaling_factor=2.5, n_group=1, topk_group=1, mlp_hidden_act="relu2", selection_bias_rate=0.001,
    num_hidden_layers=9, hybrid_override_pattern="MEMEM*EME", experts_held_first=4, num_experts_held=4,
    positions=L, hops=2, target_center=15.0, target_scale=1.0, expert_blocks=2, attn_block=8, dtype="float32",
)


@pytest.fixture(scope="module")
def ref():
    return bench.load_module("reference", NAME)


@pytest.fixture(scope="module")
def cfg():
    return bench.load_module("configs", NAME).model_config(M)
