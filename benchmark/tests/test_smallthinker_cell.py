"""The cell ``smallthinker-21b-a3b-t4.stream-packed-16k`` on the CPU at a
tiny size: its configuration's file against the catalog, ``step_flops`` and
``attention_flops`` against a count from the shapes by hand, its rehearsal
through ``run.measure`` (sound, a half batch left out of the timed path,
the fp8 control and the half batch planted in the reference), its two
readers, and its train dispatch compiled at the real size for a described
``v5e:2x2`` (nothing runs, no chip needed).  The cuts are
``smallthinker_tiny_cuts.json``'s, beside this file."""

import os
import re

import jax
import pytest

from benchmark import run
from benchmark.tests import test_rehearsal as rehearsal
from benchmark.tools import controls

os.environ.setdefault("TPU_LOG_DIR", "disabled")

NAME = "smallthinker-21b-a3b-t4"
WORKLOAD = f"{NAME}.stream-packed-16k"
SEED = 2**31 + 977      # the driver's seeds are larger than 32 signed bits hold


def _config():
    return run.load_json(run.HERE, "configs", f"{NAME}.json"), run.load_module("configs", NAME)


def tiny_cell():
    cell, config = run.load_cell_files(WORKLOAD)
    cut = run.load_json(os.path.dirname(__file__), "smallthinker_tiny_cuts.json")
    config["model"].update(cut["model"])
    config["graph"].update(cut["graph"])
    cell["traffic"].update(cut["traffic"])
    cell["driver_params"].update(cut["driver_params"])
    return cell, config


# -- the configuration's file ------------------------------------------------------


def test_file_holds_every_published_number_and_lists_the_three_cuts():
    import json

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    cfg, _ = _config()
    with open(catalog) as f:
        row = next(json.loads(line) for line in f if '"SmallThinker-21BA3B-Instruct"' in line)
    assert cfg["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differ == sorted(cfg["reduced"]) == ["moe_num_primary_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    # the program's block says the same: every width, the router as wide as
    # published, the first period of the two lists
    m = cfg["model"]
    same = [k for k in m if k in row["config"] and k not in ("num_hidden_layers", "rope_layout", "sliding_window_layout")]
    assert all(m[k] == row["config"][k] for k in same) and len(same) >= 12
    assert m["moe_num_primary_experts"] == 64 and m["num_experts_held"] == cfg["moe_num_primary_experts"] == 16
    assert m["num_hidden_layers"] == cfg["num_hidden_layers"] == 4
    assert m["rope_layout"] == row["config"]["rope_layout"][:4] == [0, 1, 1, 1]
    assert m["sliding_window_layout"] == row["config"]["sliding_window_layout"][:4] == [0, 1, 1, 1]
    assert cfg["graph"]["num_nodes"] == cfg["vocab_size"] == 37984 == 151936 // 4
    assert m["positions"] == row["config"]["max_position_embeddings"] == m["stream_length"]["max"]


def test_step_flops_and_attention_flops_against_a_hand_count():
    cfg, mod = _config()
    m = cfg["model"]
    win, full = mod.keys_attended(m, True), mod.keys_attended(m, False)
    assert 2400 < win < 2700 and 3400 < full < 3800 and win < 4096       # 2,538 and 3,612 by the law
    # 2560 -> 28 x 128 and back, 2 x 2560 -> 4 x 128, scores and values over the keys attended
    proj = 2 * 2560 * 3584 + 2 * 2560 * 512
    assert proj == 20_971_520
    # the router and six slots of which a quarter is held, three matrices of 2560 x 768 each
    moe = 2560 * 64 + 6 * (16 / 64) * 3 * 2560 * 768
    assert mod.layer_macs_per_record(m, True) == pytest.approx(proj + 2 * 28 * 128 * win + moe)
    assert mod.layer_macs_per_record(m, False) == pytest.approx(proj + 2 * 28 * 128 * full + moe)
    macs = 125 * 2560 + 3 * (proj + 7168 * win + moe) + (proj + 7168 * full + moe) + 2560 + 124
    assert mod.macs_per_record(m, 12) == pytest.approx(macs)
    assert mod.step_flops(m, cfg["graph"], 32768) == pytest.approx(6 * macs * 32768)
    assert mod.step_flops(m, cfg["graph"], 1) / 1e9 == pytest.approx(1.20, abs=0.01)
    # attention's core is two fifths of the operations
    assert 0.38 < 7168 * (3 * win + full) / macs < 0.44
    # two products forward, four backward, 28 heads of 128, 2 FLOP a MAC
    assert mod.attention_flops(m, 1000) == 6 * 2 * 28 * 128 * 1000
    assert mod.expert_flops(m, 3072) == 6 * 3 * 2560 * 768 * 3072
    assert mod.expert_bytes(m, 0, 1) == 16 * 3 * 2560 * 768 * 8


def test_model_config_tells_the_program_each_layers_kind():
    cfg, mod = _config()
    c = mod.model_config(cfg["model"])
    assert [(k.kind, k.window, k.rope) for k in c.layers] == [("attention", 0, False)] + [("attention", 4096, True)] * 3
    assert (c.hidden_act, c.softmax_after_topk, c.router_before_attention) == ("relu", True, True)
    assert not (c.attention_gate or c.qk_norm or c.shared_expert_intermediate_size)
    assert c.experts_held == (0, 16) and c.num_experts == 64 and c.num_experts_per_tok == 6


# -- the rehearsal ------------------------------------------------------------------------------


def _measure(trace=False, seconds=0.5):
    cell, config = tiny_cell()
    return run.measure(cell, config, SEED, seconds, trace, jax.devices()[:1])


def test_sound_run_is_correct_and_counts_whole_units():
    got = _measure()
    assert got["correct"], got["compared"]
    window = got["run"].window
    assert window.steps == window.launches * window.extras["steps_per_launch"] > 0
    assert window.records == window.steps * 256
    assert "stream/attn/core" in window.extras["program_text"]
    assert run.load_module("metrics", "records_count_gap").read(got["run"]) == 0


def test_half_a_batch_left_out_of_the_timed_path_is_not_correct(monkeypatch):
    from dragonfly2_tpu.trainer import online_graph, train

    broken = rehearsal.FAULTS["half_batch_left_out"](train._graph_train_step)
    monkeypatch.setattr(train, "_graph_train_step", broken)
    monkeypatch.setattr(online_graph, "_graph_train_step", broken)
    got = _measure()
    assert not got["correct"], got["compared"]


@pytest.mark.parametrize("variant", ["fp8", "half_batch"])
def test_control_and_fault_in_the_reference_are_not_correct(variant):
    cell, config = tiny_cell()
    got = controls.readings(cell, config, SEED, [variant], jax.devices()[:1])[variant]
    failed = [k for k, v in got.items() if k in cell["limits"] and v > cell["limits"][k]]
    assert failed, got


def test_traced_run_reads_the_keys_from_the_spans_and_no_device_scope():
    """A CPU trace has no device plane: the roofline reader says nothing,
    the fill share reads what the ledger put on the dispatches' spans."""
    got = _measure(trace=True)
    r = got["run"]
    r.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert not r.trace.devices
    for name in ("attn_core_share", "attn_core_roofline", "moe_experts_roofline", "step_mfu"):
        assert run.load_module("metrics", name).read(r) is None
    fill = run.load_module("metrics", "attn_band_fill_share").read(r)
    assert fill is not None and 30 < fill < 100
    # 4 experts of 16 held: 25 under an even router
    assert 5 < run.load_module("metrics", "moe_slots_held_share").read(r) < 60


def test_readers_find_nothing_in_a_program_that_counts_no_keys():
    """The parent of the PR that brought them has no such attribute on its
    spans and no ``attention_flops``: ``None``, and nothing raised."""
    from types import SimpleNamespace

    from benchmark.reduce import stream_scopes

    r = SimpleNamespace(
        trace=None, cell={"config": "qwen3-next-80b-a3b-t16"}, config={"model": {}}, peaks={"bf16_flops_per_s": 1.0},
        window=SimpleNamespace(extras={"stream_scope_s": {"attn/core": 1.0, "busy": 2.0}}),
    )
    assert stream_scopes.window_dispatches(r, "attn_keys_attended_window") == []
    assert run.load_module("metrics", "attn_core_roofline").read(r) is None
    assert run.load_module("metrics", "attn_band_fill_share").read(r) is None


# -- the dispatch at its real size, compiled for the chip ---------------------------------------

GB = 1e9
# By the compiler's count for the described chip (PR 31): parameters and
# both moments 7.89 GB, the step's temporaries 7.14 GB of which 2.63 GB are
# the gradients, together 15.04 GB of the chip's 16.9.
WANT_GB = 15.04


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps the description away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_dispatch_compiles_for_the_chip_with_one_body_a_loop(topo):
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from dragonfly2_tpu.models import build_ranker
    from dragonfly2_tpu.models.gnn import NeighborTable
    from dragonfly2_tpu.trainer.train import (
        TrainConfig, TrainState, _graph_train_step, _make_optimizer,
    )

    cell, config = run.load_cell_files(WORKLOAD)
    g, m, p = config["graph"], config["model"], cell["driver_params"]
    m["positions"] = p["batch_size"] // p["rows"]
    assert m["positions"] == 16384
    ranker = build_ranker(run.load_module("configs", cell["config"]).model_config(m))
    one = SingleDeviceSharding(topo.devices[0])
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    n, k = g["num_nodes"], g["max_neighbors"]
    nf = spec((n, g["node_feature_dim"] * (1 + 2 * m["hops"]) + 2), jnp.float32)
    table = NeighborTable(spec((n, k), jnp.int32), spec((n, k), jnp.float32), spec((n, k, 1), jnp.float32))

    def make_state():
        z = lambda s: jnp.zeros(s.shape, s.dtype)
        ids = jnp.zeros((2,), jnp.int32)
        v = ranker.module.init(jax.random.PRNGKey(0), z(nf), jax.tree_util.tree_map(z, table), ids, ids)
        return TrainState.create(
            apply_fn=ranker.module.apply, params=v["params"], tx=_make_optimizer(TrainConfig(), 1000),
            dropout_rng=jax.random.PRNGKey(1), aux=v.get("aux"),
        )

    state = jax.tree_util.tree_map(lambda s: spec(s.shape, s.dtype), jax.eval_shape(make_state))
    params = sum(s.size for s in jax.tree_util.tree_leaves(state.params))
    assert params == pytest.approx(656.5e6, rel=0.002)          # 10.5 GB at 16 B a parameter

    def dispatch(state, hop, table, es, ed, y):       # OnlineGraphTrainer._train_dispatch
        def body(carry, xs):
            a, b, t = xs
            return _graph_train_step(carry, hop, table, a, b, t, ranker.query_feats(b, t))
        return jax.lax.scan(body, state, (es, ed, y))

    shape = (p["super_steps"], p["batch_size"])
    compiled = jax.jit(dispatch, donate_argnums=(0,)).lower(
        state, nf, table, spec(shape, jnp.int32), spec(shape, jnp.int32), spec(shape, jnp.float32)
    ).compile()
    text = compiled.as_text()
    assert "ragged-dot" in text
    # Attention's products: a layer has a forward, a recomputed forward and
    # a backward (2 + 2 + 5 = 9 products), one body each whatever the row's
    # 528 block pairs; unrolled, four layers would hold 4 x 528 x 9.
    products = [
        line for line in text.splitlines()
        if "stream/attn/core" in line and re.search(r"= \S+ (convolution|dot)\(", line)
    ]
    assert 0 < len(products) <= 4 * 9 * 2, len(products)
    mem = compiled.memory_analysis()
    got = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    ) / GB
    assert got == pytest.approx(WANT_GB, rel=0.1), f"{got:.2f} GB"
    assert got < 16.9
