"""The stream ranker's tiny sizes, seeded records and the cheap fixtures
that ``tests/test_stream_reference.py`` and ``tests/test_stream_ranker.py``
share: every width cut, every ratio kept.  Two files so that ``--dist
loadfile`` can give them to two workers."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench
from dragonfly2_tpu.models import StreamRankerConfig

NAME = "qwen3-next-80b-a3b-t16"
N, HOP_DIM, L, ROWS = 48, 10, 32, 4
B = ROWS * L

# Every width cut, every ratio kept: 2 value heads a key head, 2 queries a
# key-value head, a quarter of the head rotary, one layer in four attention,
# a quarter of the experts held.
M = dict(
    hidden_size=32, num_hidden_layers=4, full_attention_interval=4, rms_norm_eps=1e-6,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8, partial_rotary_factor=0.25,
    rope_theta=1e7, linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=8, linear_conv_kernel_dim=4, num_experts=16, num_experts_per_tok=3,
    moe_intermediate_size=16, shared_expert_intermediate_size=16, norm_topk_prob=True,
    experts_held_first=4, num_experts_held=4, positions=L, hops=2, dtype="float32",
    target_center=15.0, target_scale=1.0,
    # Two blocks of B slots whatever the routing: a quarter of 3 B slots is
    # held, so the second is all padding; a forced router overflows both.
    expert_blocks=2,
)


@pytest.fixture(scope="module")
def ref():
    return bench.load_module("reference", NAME)


@pytest.fixture(scope="module")
def cfg() -> StreamRankerConfig:
    got = bench.load_module("configs", NAME).model_config(M)
    return dataclasses.replace(got, chunk=8, attn_block=8)


def _records(seed=0, rows=ROWS):
    """Packed streams: segments of uneven length, several starting inside a
    chunk of 8, one row that is a single segment."""
    rng = np.random.default_rng(seed)
    dst = np.zeros((rows, L), np.int32)
    for r in range(rows - 1):
        cuts = np.sort(rng.choice(np.arange(1, L), size=3, replace=False))
        dst[r] = np.searchsorted(cuts, np.arange(L), side="right") + 4 * r
    dst[rows - 1] = 40
    src = rng.integers(0, N, (rows, L)).astype(np.int32)
    y = rng.normal(15.0, 1.0, (rows, L)).astype(np.float32)
    return src.reshape(-1), dst.reshape(-1), y.reshape(-1)


@pytest.fixture(scope="module")
def hop():
    return jnp.asarray(np.random.default_rng(1).normal(size=(N, HOP_DIM)).astype(np.float32))
