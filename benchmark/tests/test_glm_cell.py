"""The cell ``glm-4-7-flash-t8.stream-packed-4k`` on the CPU at a tiny
size: its configuration's file against the catalog, ``step_flops`` against
a count from the reference's own parameter list, ``attention_flops`` and
``latent_flops`` against a count by hand, its rehearsal through
``run.measure`` (sound, a half batch left out of the timed path, the fp8
control and the half batch planted in the reference), its three readers,
and its train dispatch compiled at the real size for a described
``v5e:2x2`` (nothing runs, no chip needed).  The cuts are
``glm_tiny_cuts.json``'s, beside this file."""

import os
import re

import jax
import pytest

from benchmark import run
from benchmark.tests import test_rehearsal as rehearsal
from benchmark.tools import controls

os.environ.setdefault("TPU_LOG_DIR", "disabled")

NAME = "glm-4-7-flash-t8"
WORKLOAD = f"{NAME}.stream-packed-4k"
SEED = 2**31 + 1987     # a seed may be larger than 32 signed bits hold


def _config():
    return run.load_json(run.HERE, "configs", f"{NAME}.json"), run.load_module("configs", NAME)


def tiny_cell():
    cell, config = run.load_cell_files(WORKLOAD)
    cut = run.load_json(os.path.dirname(__file__), "glm_tiny_cuts.json")
    config["model"].update(cut["model"])
    config["graph"].update(cut["graph"])
    cell["traffic"].update(cut["traffic"])
    cell["driver_params"].update(cut["driver_params"])
    return cell, config


# -- the configuration's file ------------------------------------------------------


def test_file_holds_every_published_number_and_lists_the_three_cuts():
    # the published GLM-4.7-Flash entry of the model catalog, kept beside this file
    row = run.load_json(os.path.dirname(__file__), "glm_catalog_entry.json")
    cfg, _ = _config()
    assert cfg["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differ == sorted(cfg["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    # the program's block says the same: every width, the router as wide as
    # published, the leading dense layer and four expert layers
    m = cfg["model"]
    same = [k for k in m if k in row["config"] and k not in ("num_hidden_layers",)]
    assert all(m[k] == row["config"][k] for k in same) and len(same) >= 18
    assert m["n_routed_experts"] == 64 and m["num_experts_held"] == cfg["n_routed_experts"] == 8
    assert m["num_hidden_layers"] == cfg["num_hidden_layers"] == 1 + 4
    assert cfg["graph"]["num_nodes"] == cfg["vocab_size"] == 19360 == 154880 // 8
    assert m["positions"] == m["stream_length"]["max"] == 4096
    assert set(cfg["assumed"]) >= {"selection_bias_rate", "loads", "rope", "expert_blocks", "group_routing"}
    assert any("multi-token" in d for d in cfg["departures"])


def _macs_from_the_references_shapes(m, hop_dim, keys):
    """One record's multiply-accumulates, read off the reference's own
    parameter list: every matrix once (a held expert's for the share of
    slots it expects), the head's one column, and attention's core over
    the keys attended."""
    ref = run.load_module("reference", NAME)
    macs = 0.0
    for name, _, shape in ref.parameter_list(m, hop_dim, 1):
        if len(shape) == 3:                  # a held expert's matrices: k of 64 slots a record
            macs += shape[0] * shape[1] * shape[2] * m["num_experts_per_tok"] / m["n_routed_experts"]
        elif len(shape) == 2 and name != "head":
            macs += shape[0] * shape[1]
    d, h = m["hidden_size"], m["num_attention_heads"]
    core = h * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]) * keys
    return macs + d + m["num_hidden_layers"] * core


def test_step_flops_match_a_count_from_the_references_shapes():
    cfg, mod = _config()
    m, g = cfg["model"], cfg["graph"]
    keys = mod.keys_attended(m)
    assert 850 < keys < 950                       # 898 by the law at 4,096 positions
    hop_dim = g["node_feature_dim"] * (1 + 2 * m["hops"]) + 2
    macs = _macs_from_the_references_shapes(m, hop_dim, keys)
    assert mod.macs_per_record(m, g["node_feature_dim"]) == pytest.approx(macs, rel=1e-9)
    assert mod.step_flops(m, g, 32768) == pytest.approx(6 * macs * 32768)
    assert mod.step_flops(m, g, 32768) / 1e12 == pytest.approx(54, abs=1.5)
    # the latent attention's four products, its output projection and core:
    # about half the operations
    latent = 2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448
    assert latent == 11_272_192 and mod.latent_macs_per_record(m) == latent
    assert 0.45 < 5 * (latent + 5120 * 2048 + 20 * 512 * keys) / macs < 0.6
    assert mod.latent_flops(m, 1000) == 6 * latent * 5 * 1000
    assert mod.attention_flops(m, 1000) == 6 * 20 * 512 * 1000
    assert mod.expert_flops(m, 2048) == 6 * 3 * 2048 * 1536 * 2048
    assert mod.expert_bytes(m, 0, 1) == 8 * 3 * 2048 * 1536 * 8


def test_model_config_tells_the_program_the_router_and_the_layers():
    cfg, mod = _config()
    c = mod.model_config(cfg["model"])
    assert [(k.kind, k.window, k.rope) for k in c.layers] == [("attention", 0, True)] * 5
    assert (c.scoring_func, c.routed_scaling_factor, c.selection_bias_rate) == ("sigmoid", 1.8, 0.001)
    assert (c.first_k_dense_replace, c.intermediate_size, c.shared_expert_intermediate_size) == (1, 10240, 1536)
    assert not (c.attention_gate or c.qk_norm or c.shared_expert_gate or c.softmax_after_topk)
    assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim) == (768, 512, 192, 64, 256)
    assert c.experts_held == (0, 8) and c.num_experts == 64 and c.num_experts_per_tok == 4
    with pytest.raises(ValueError, match="noaux_tc"):
        mod.model_config({**cfg["model"], "topk_method": "greedy"})


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
    assert "stream/attn/latent" in window.extras["program_text"]
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


def test_traced_run_reads_the_routes_from_the_spans_and_no_device_scope():
    """A CPU trace has no device plane: the latent readers say nothing,
    the route reader reads what the ledger put on the dispatches' spans."""
    got = _measure(trace=True)
    r = got["run"]
    r.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert not r.trace.devices
    for name in ("attn_latent_share", "attn_latent_roofline", "attn_core_share", "moe_experts_roofline", "step_mfu"):
        assert run.load_module("metrics", name).read(r) is None
    assert run.load_module("metrics", "moe_route_max_over_mean").read(r) > 1
    # 4 experts of 16 held: 25 under an even router
    assert 5 < run.load_module("metrics", "moe_slots_held_share").read(r) < 60
    assert 30 < run.load_module("metrics", "attn_pairs_run_share").read(r) <= 100
    # the latent layers count their keys as kind full: the segments leave
    # a part of the causal band
    assert 0 < run.load_module("metrics", "attn_band_fill_share").read(r) < 100


def test_readers_find_nothing_in_a_program_that_has_no_latent_scope_or_route_count():
    """The parent of the PR that brought them has no such scope and no such
    attribute on its spans: ``None``, and nothing raised."""
    from types import SimpleNamespace

    from benchmark.reduce import stream_scopes

    r = SimpleNamespace(
        trace=None, cell={"config": "qwen3-next-80b-a3b-t16"}, config={"model": {}}, peaks={"bf16_flops_per_s": 1.0},
        window=SimpleNamespace(extras={"stream_scope_s": {"attn/core": 1.0, "busy": 2.0}}),
    )
    assert stream_scopes.window_dispatches(r, "moe_route_max", "moe_route_mean") == []
    for name in ("attn_latent_share", "attn_latent_roofline", "moe_route_max_over_mean"):
        assert run.load_module("metrics", name).read(r) is None


def test_latent_roofline_reads_the_windows_records_against_the_scopes_time(monkeypatch):
    from types import SimpleNamespace

    from benchmark.reduce import stream_scopes

    cfg, mod = _config()
    monkeypatch.setattr(stream_scopes, "window_dispatches", lambda run, *names: [(65536,), (65536,)])
    r = SimpleNamespace(
        trace=None, cell={"config": NAME}, config=cfg, peaks={"bf16_flops_per_s": 197e12},
        window=SimpleNamespace(extras={"stream_scope_s": {"attn/latent": 0.5, "busy": 4.0}}),
    )
    want = 100.0 * mod.latent_flops(cfg["model"], 2 * 65536) / 197e12 / 0.5
    assert run.load_module("metrics", "attn_latent_roofline").read(r) == pytest.approx(want)
    assert run.load_module("metrics", "attn_latent_share").read(r) == pytest.approx(12.5)


# -- the dispatch at its real size, compiled for the chip ---------------------------------------

GB = 1e9
# By the compiler's count for the described chip: parameters and both
# moments 7.10 GB, the step's temporaries 7.34 GB, together 14.44 GB of
# the chip's 16.9.
WANT_GB = 14.44


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps the description away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_dispatch_compiles_for_the_chip_within_its_memory(topo):
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
    assert m["positions"] == 4096
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
            model_state={c: v[c] for c in v if c not in ("params", "aux")},
        )

    state = jax.tree_util.tree_map(lambda s: spec(s.shape, s.dtype), jax.eval_shape(make_state))
    params = sum(s.size for s in jax.tree_util.tree_leaves(state.params))
    assert params == pytest.approx(591.5e6, rel=0.002)          # 9.46 GB at 16 B a parameter

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
    # The four latent products of five layers: forward, recomputed forward
    # and two backward products each, none of them unrolled per block.
    latent = [
        line for line in text.splitlines()
        if "stream/attn/latent" in line and re.search(r"= \S+ (convolution|dot)\(", line)
    ]
    assert 0 < len(latent) <= 5 * 4 * 4 * 2, len(latent)
    mem = compiled.memory_analysis()
    got = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    ) / GB
    assert got == pytest.approx(WANT_GB, rel=0.1), f"{got:.2f} GB"
    assert got < 16.9
