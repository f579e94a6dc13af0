"""The SmallThinker configuration's tiny sizes, seeded records and the
fixtures that ``tests/test_smallthinker_reference.py`` and
``tests/test_window_attention.py`` share: every width cut, every ratio and
both layer kinds kept; a window (12) shorter than a row (32) whose edge
falls inside a block of 8; the last row one segment longer than the
window."""

import pytest

from benchmark import run as bench
from tests._stream_sizes import HOP_DIM, L, N, ROWS, _records, hop  # noqa: F401 — re-exported

NAME = "smallthinker-21b-a3b-t4"
B = ROWS * L

M = dict(
    hidden_size=32, rms_norm_eps=1e-6, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    rope_theta=1.5e6, rope_layout=[0, 1, 1, 1], sliding_window_layout=[0, 1, 1, 1],
    sliding_window_size=12, moe_num_primary_experts=16, moe_num_active_primary_experts=3,
    moe_ffn_hidden_size=16, moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    num_hidden_layers=4, experts_held_first=4, num_experts_held=4, positions=L, hops=2,
    target_center=15.0, target_scale=1.0, expert_blocks=2, attn_block=8, dtype="float32",
)


@pytest.fixture(scope="module")
def ref():
    return bench.load_module("reference", NAME)


@pytest.fixture(scope="module")
def cfg():
    return bench.load_module("configs", NAME).model_config(M)
