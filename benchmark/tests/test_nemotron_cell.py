"""The cell ``nemotron-3-nano-30b-a3b-t16.stream-packed-8k`` on the CPU at
a tiny size: its configuration's file against the catalog, ``step_flops``
against a count from the reference's own parameter list, the SSD's
counts by hand, its rehearsal through ``run.measure`` (sound,
a half batch left out of the timed path, the fp8 control and the half
batch planted in the reference), its readers, and its train dispatch
compiled at the real size for a described ``v5e:2x2`` (nothing runs, no
chip needed).  The cuts are ``nemotron_tiny_cuts.json``'s, beside this
file."""

import os

import jax
import pytest

from benchmark import run
from benchmark.tests import test_rehearsal as rehearsal
from benchmark.tools import controls

os.environ.setdefault("TPU_LOG_DIR", "disabled")

NAME = "nemotron-3-nano-30b-a3b-t16"
WORKLOAD = f"{NAME}.stream-packed-8k"
SEED = 2**31 + 1987     # a seed may be larger than 32 signed bits hold


def _config():
    return run.load_json(run.HERE, "configs", f"{NAME}.json"), run.load_module("configs", NAME)


def tiny_cell():
    cell, config = run.load_cell_files(WORKLOAD)
    cut = run.load_json(os.path.dirname(__file__), "nemotron_tiny_cuts.json")
    config["model"].update(cut["model"])
    config["graph"].update(cut["graph"])
    cell["traffic"].update(cut["traffic"])
    cell["driver_params"].update(cut["driver_params"])
    return cell, config


# -- the configuration's file ------------------------------------------------------


def test_file_holds_every_published_value_and_lists_the_three_cuts():
    # the published Nemotron-3-Nano entry of the model catalog, kept beside this file
    row = run.load_json(os.path.dirname(__file__), "nemotron_catalog_entry.json")
    cfg, _ = _config()
    assert cfg["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differ == sorted(cfg["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    # the program's block says the same: every width, the router as wide as
    # published, the first nine layers of the pattern
    m = cfg["model"]
    same = [k for k in m if k in row["config"] and k not in ("num_hidden_layers", "hybrid_override_pattern")]
    assert all(m[k] == row["config"][k] for k in same) and len(same) >= 20
    assert m["hybrid_override_pattern"] == row["config"]["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert m["n_routed_experts"] == 128 and m["num_experts_held"] == cfg["n_routed_experts"] == 8
    assert m["num_hidden_layers"] == cfg["num_hidden_layers"] == 9
    assert cfg["graph"]["num_nodes"] == cfg["vocab_size"] == 16384 == 131072 // 8
    assert m["positions"] == m["stream_length"]["max"] == 8192
    assert set(cfg["assumed"]) >= {"positions", "gated_norm", "selection_bias_rate", "expert_blocks", "initialisation"}
    assert "sixteen chips share each layer" in cfg["deployment"]


def _macs_from_the_references_shapes(m, hop_dim, keys):
    """One record's multiply-accumulates, read off the reference's own
    parameter list: every matrix once (a held expert's for the share of
    slots it expects), the head's one column, attention's core over the
    keys attended and the SSD's products."""
    ref = run.load_module("reference", NAME)
    macs = 0.0
    for name, _, shape in ref.parameter_list(m, hop_dim, 1):
        if len(shape) == 3:                  # a held expert's matrices: k of 128 slots a record
            macs += shape[0] * shape[1] * shape[2] * m["num_experts_per_tok"] / m["n_routed_experts"]
        elif len(shape) == 2 and name != "head" and not name.endswith("mamba.conv"):
            macs += shape[0] * shape[1]
    core = 2 * m["num_attention_heads"] * m["head_dim"] * keys
    c, ng, n, h, p = m["chunk_size"], m["n_groups"], m["ssm_state_size"], m["mamba_num_heads"], m["mamba_head_dim"]
    ssd = ng * (c + 1) / 2 * n + h * (c + 1) / 2 * p + 2 * h * n * p
    return macs + m["hidden_size"] + core + 4 * ssd


def test_step_flops_match_a_count_from_the_references_shapes():
    cfg, mod = _config()
    m, g = cfg["model"], cfg["graph"]
    keys = mod.keys_attended(m)
    assert 1500 < keys < 2000
    hop_dim = g["node_feature_dim"] * (1 + 2 * m["hops"]) + 2
    macs = _macs_from_the_references_shapes(m, hop_dim, keys)
    assert mod.macs_per_record(m, g["node_feature_dim"]) == pytest.approx(macs, rel=1e-9)
    assert mod.step_flops(m, g, 32768) == pytest.approx(6 * macs * 32768)
    # the four Mamba-2 layers' projections: about half the operations
    mamba = 2688 * (4096 + 6144 + 64) + 4096 * 2688
    assert mamba == 38_707_200 and 0.45 < 4 * mamba / macs < 0.6
    # the SSD: 8 x 64.5 x 128 + 64 x 64.5 x 64 + 2 x 64 x 128 x 64 a record and layer
    assert mod.ssd_macs_per_record(m) == 66_048 + 264_192 + 1_048_576
    assert mod.ssd_flops(m, 1000) == 6 * 4 * 1000 * mod.ssd_macs_per_record(m)
    assert mod.ssd_bytes(m, 1) == 4 * ((2 * 6144 + 256 + 16384) * 2 + 4 * (6144 + 64))
    # attention's core: 32 heads of 128, scores and values forward, four products backward
    assert mod.attention_flops(m, 1000) == 2 * 32 * 128 * (2 + 4) * 1000


def test_model_config_tells_the_program_the_layers_and_the_router():
    cfg, mod = _config()
    c = mod.model_config(cfg["model"])
    assert [k.kind for k in c.layers] == ["mamba", "none", "mamba", "none", "mamba", "attention", "none", "mamba", "none"]
    assert not c.layers[5].rope and c.feed_forwards.count("experts") == 4
    assert (c.scoring_func, c.routed_scaling_factor, c.selection_bias_rate, c.hidden_act) == ("sigmoid", 2.5, 0.001, "relu2")
    assert (c.moe_intermediate_size, c.shared_expert_intermediate_size, c.num_experts_per_tok) == (1856, 3712, 6)
    assert not (c.attention_gate or c.qk_norm or c.shared_expert_gate or c.softmax_after_topk)
    assert (c.mamba_num_heads, c.mamba_head_dim, c.mamba_n_groups, c.ssm_state_size, c.chunk) == (64, 64, 8, 128, 128)
    assert c.experts_held == (0, 8) and c.num_experts == 128 and c.rms_norm_eps == 1e-5
    with pytest.raises(ValueError, match="relu2"):
        mod.model_config({**cfg["model"], "mlp_hidden_act": "silu"})
    with pytest.raises(ValueError, match="letters"):
        mod.model_config({**cfg["model"], "num_hidden_layers": 8})


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
    assert "stream/mamba/scan" in window.extras["program_text"]
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


def test_traced_run_reads_the_spans_and_no_device_scope():
    """A CPU trace has no device plane: the Mamba-2 readers say nothing,
    the span readers read what the ledger put on the dispatches' spans."""
    got = _measure(trace=True)
    r = got["run"]
    r.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert not r.trace.devices
    for name in ("ssm_mixer_share", "ssm_scan_roofline", "attn_core_share", "attn_core_roofline", "moe_share", "step_mfu"):
        assert run.load_module("metrics", name).read(r) is None
    assert run.load_module("metrics", "moe_route_max_over_mean").read(r) > 1
    # 4 experts of 16 held: 25 under an even router
    assert 5 < run.load_module("metrics", "moe_slots_held_share").read(r) < 60
    assert 0 < run.load_module("metrics", "attn_pairs_run_share").read(r) <= 100
    assert 0 < run.load_module("metrics", "attn_band_fill_share").read(r) < 100


def test_mamba_readers_find_nothing_in_a_program_without_the_scope():
    """The parent of the PR that brought them has no such scope: ``None``,
    and nothing raised."""
    from types import SimpleNamespace

    r = SimpleNamespace(
        trace=None, cell={"config": "glm-4-7-flash-t8"}, config={"model": {}}, peaks={"bf16_flops_per_s": 1.0},
        window=SimpleNamespace(extras={"op_name_s": ({"jit(d)/stream/attn/core/dot": 1.0, None: 1.0}, 2.0)}),
    )
    for name in ("ssm_mixer_share", "ssm_scan_roofline"):
        assert run.load_module("metrics", name).read(r) is None


def test_mamba_readers_read_the_scopes_time(monkeypatch):
    from types import SimpleNamespace

    from benchmark.reduce import stream_scopes

    cfg, mod = _config()
    monkeypatch.setattr(stream_scopes, "window_dispatches", lambda run, *names: [(65536,), (65536,)])
    names = {
        "jit(dispatch)/while/body/stream/mamba/proj/dot": 1.0,
        "jit(dispatch)/while/body/transpose(jvp(stream))/mamba/scan/while/body/dot": 0.25,
        "jit(dispatch)/while/body/stream/mamba/scan/exp": 0.25,
        "jit(dispatch)/while/body/stream/attn/core/dot": 2.0,
        None: 0.5,
    }
    r = SimpleNamespace(
        trace=None, cell={"config": NAME}, config=cfg, peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        window=SimpleNamespace(extras={"op_name_s": (names, 4.0), "stream_scope_s": {"attn/core": 2.0, "busy": 4.0}}),
    )
    assert run.load_module("metrics", "ssm_mixer_share").read(r) == pytest.approx(37.5)
    m = cfg["model"]
    least = max(mod.ssd_flops(m, 131072) / 197e12, mod.ssd_bytes(m, 131072) / 819e9)
    assert run.load_module("metrics", "ssm_scan_roofline").read(r) == pytest.approx(100.0 * least / 0.5)
    # the keys of both kinds the stub hands every name, over the core's 2 s
    attended = mod.attention_flops(m, 2 * 131072) / 197e12
    assert run.load_module("metrics", "attn_core_roofline").read(r) == pytest.approx(100.0 * attended / 2.0)


# -- the dispatch at its real size, compiled for the chip ---------------------------------------

GB = 1e9
# The compiler's peak for the described chip, parameters and both moments
# (8.01 GB) among it: 14.46 GB at 4 rows of 8,192 (12.82 at 2 rows, where
# the chip read 13.25).  Not memory_analysis()'s arguments plus
# temporaries: its temporaries count 3.5 GB that the buffer assignment
# never holds at once (18.31 GB at 4 rows).
WANT_GB = 14.46


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
    assert m["positions"] == 8192 and p["rows"] == 4
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
    assert params == pytest.approx(667.3e6, rel=0.002)          # 10.68 GB at 16 B a parameter

    def dispatch(state, hop, table, es, ed, y):       # OnlineGraphTrainer._train_dispatch
        def body(carry, xs):
            a, b, t = xs
            return _graph_train_step(carry, hop, table, a, b, t, ranker.query_feats(b, t))
        return jax.lax.scan(body, state, (es, ed, y))

    shape = (p["super_steps"], p["batch_size"])
    compiled = jax.jit(dispatch, donate_argnums=(0,)).lower(
        state, nf, table, spec(shape, jnp.int32), spec(shape, jnp.int32), spec(shape, jnp.float32)
    ).compile()
    mem = compiled.memory_analysis()
    got = mem.peak_memory_in_bytes / GB
    assert got == pytest.approx(WANT_GB, rel=0.05), f"{got:.2f} GB"
    assert mem.argument_size_in_bytes / GB < got < 16.9
