"""The FLOPs-from-shapes functions against a count by hand."""

import json
import os

import pytest

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))


def _config(name):
    return run.load_json(run.HERE, "configs", f"{name}.json"), run.load_module("configs", name)


def test_hop_h1024_is_18_9_mflop_a_record():
    cfg, mod = _config("hop-h1024")
    # encoder: (62 + 32) x 1024 + 1024 x 1024 + 1024 x 64, on both endpoints;
    # head: 192 x 1024 + 1024 x 512 + 512.
    encoder = 94 * 1024 + 1024 * 1024 + 1024 * 64
    head = 192 * 1024 + 1024 * 512 + 512
    assert mod.macs_per_record(cfg["model"], 12) == 2 * encoder + head == 3_142_144
    per_record = mod.step_flops(cfg["model"], cfg["graph"], 1)
    assert per_record == 6 * 3_142_144
    assert per_record / 1e6 == pytest.approx(18.9, abs=0.06)


def test_hop_h1024_is_within_a_tenth_of_the_compilers_count():
    # PR 21's chip run: cost_analysis() of the bare step at batch 131072 gave
    # 2.594e12.  A sanity check of the hand count, nothing the benchmark prints.
    cfg, mod = _config("hop-h1024")
    assert mod.step_flops(cfg["model"], cfg["graph"], 131_072) == pytest.approx(2.594e12, rel=0.1)


def test_gat_c2_counts_the_whole_graph_every_step():
    cfg, mod = _config("gat-c2")
    m, g = cfg["model"], cfg["graph"]
    layer1 = 44 * 128 + 2 * 16 * 44 * 128 + 16 * 4 + 2 * 16 * 128 + 128 * 128
    layer2 = 128 * 128 + 2 * 16 * 128 * 128 + 16 * 4 + 2 * 16 * 128 + 128 * 128
    assert mod.macs_per_node(m, 12, 16) == layer1 + layer2 + 128 * 64 == 775_808
    assert mod.macs_per_record(m) == 192 * 128 + 128 * 64 + 64
    want = 6 * (775_808 * g["num_nodes"] + 32_832 * 131_072)
    assert mod.step_flops(m, g, 131_072) == want
    # The graph's part does not move with the batch.
    assert mod.step_flops(m, g, 262_144) - want == 6 * 32_832 * 131_072
