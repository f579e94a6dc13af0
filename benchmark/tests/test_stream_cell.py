"""The cell ``qwen3-next-80b-a3b-t16.stream-packed-4k`` on the CPU at a
tiny size: its reference against the flax module, its traffic, its
``step_flops`` against a hand count, and its rehearsal through
``run.measure`` with ``test_rehearsal.py``'s faults and a lower-precision
control planted, under the cuts the configuration's file lists as ``tiny``
(``test_rehearsal.py`` drives the cell too, at the published widths, where
``tiny.py`` cuts nothing of it)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run, traffic
from benchmark.tests import stream_tiny
from benchmark.tests import test_rehearsal as rehearsal
from benchmark.tools import controls

NAME = "qwen3-next-80b-a3b-t16"
SEED = 2**31 + 123      # the driver's seeds are larger than 32 signed bits hold


def _config():
    return run.load_json(run.HERE, "configs", f"{NAME}.json"), run.load_module("configs", NAME)


# -- the configuration's file ------------------------------------------------------


def test_file_holds_every_published_number_and_lists_the_three_cuts():
    import json
    import os

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    cfg, _ = _config()
    with open(catalog) as f:
        row = next(json.loads(line) for line in f if '"Qwen3-Next-80B-A3B-Instruct"' in line)
    assert cfg["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differ == sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    # the program's block says the same, with the router as wide as published
    m = cfg["model"]
    same = [k for k in m if k in row["config"] and k not in ("num_hidden_layers", "num_experts")]
    assert all(m[k] == row["config"][k] for k in same) and len(same) >= 16
    assert m["num_experts"] == 512 and m["num_experts_held"] == cfg["num_experts"] == 32
    assert m["num_hidden_layers"] == cfg["num_hidden_layers"] == 4
    assert cfg["graph"]["num_nodes"] == cfg["vocab_size"] == 18992


def test_step_flops_against_a_hand_count():
    cfg, mod = _config()
    m = dict(cfg["model"], positions=4096)
    # Gated DeltaNet: 2048 -> 12288 and 64, 4 taps over 8192 channels, 4096 -> 2048,
    # and S^T k, k u^T, S^T q over 32 heads of 128 x 128.
    gdn = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 4096 * 2048 + 3 * 32 * 128 * 128
    # gated attention: 2048 -> 8192, 2 x 2048 -> 512, 4096 -> 2048, and scores and
    # values over the keys a query attends.
    keys = mod.keys_attended(m)
    assert 500 < keys < 900          # half the length-weighted mean stream, cut at 4096
    attn = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 16 * 256 * keys
    # router, shared expert and its gate, and ten slots of which a sixteenth is held
    moe = 2048 * 512 + 3 * 2048 * 512 + 2048 + 10 * (32 / 512) * 3 * 2048 * 512
    assert mod.layer_macs_per_record(m, False) == gdn + moe
    assert mod.layer_macs_per_record(m, True) == pytest.approx(attn + moe)
    macs = 125 * 2048 + 3 * (gdn + moe) + (attn + moe) + 2048 + 124
    assert mod.macs_per_record(m, 12) == pytest.approx(macs)
    assert mod.step_flops(m, cfg["graph"], 32768) == pytest.approx(6 * macs * 32768)
    assert mod.step_flops(m, cfg["graph"], 1) / 1e9 == pytest.approx(0.98, abs=0.01)
    # the grouped product: three matrices of 2048 x 512 a slot, forward and backward
    assert mod.expert_flops(m, 640) == 6 * 3 * 2048 * 512 * 640
    assert mod.expert_bytes(m, 0, 1) == 32 * 3 * 2048 * 512 * 8


# -- the traffic ---------------------------------------------------------------------


def test_streams_are_packed_skewed_and_the_same_for_the_same_seed():
    drv = run.load_module("drivers", "stream")
    cluster = traffic.make_cluster(2000, 5)
    law = {"median": 512, "sigma": 1.0, "min": 8, "max": 4096}
    blocks = drv.stream_blocks(cluster, 4, 65536, law, 1.0, SEED)
    again = drv.stream_blocks(cluster, 4, 65536, law, 1.0, SEED)
    assert len(blocks) == 4 and all(len(a) == 65536 for b in blocks for a in b)
    for b, c in zip(blocks, again):
        for x, y in zip(b, c):
            np.testing.assert_array_equal(x, y)
    src, dst, y = (np.concatenate(p) for p in zip(*blocks))
    assert (src != dst).all() and np.isfinite(y).all() and (y > 0).all()
    runs = np.diff(np.flatnonzero(np.concatenate([[True], dst[1:] != dst[:-1], [True]])))
    assert runs[:-1].min() >= 8 and runs.max() <= 4096 and 300 < np.median(runs) < 800
    # Zipf over hosts ranked with the seed peers first: the busiest parent is a seed peer
    counts = np.bincount(src, minlength=cluster.n)
    assert cluster.host_type[counts.argmax()] == 1 and counts.max() > 0.08 * len(src)
    other = drv.stream_blocks(cluster, 4, 65536, law, 1.0, SEED + 1)
    assert not np.array_equal(other[0][0], blocks[0][0])


# -- the reference against the flax module ----------------------------------------------------


def test_reference_forward_matches_flax_at_a_tiny_size():
    from dragonfly2_tpu.models import stream

    _, mod = _config()
    R = run.load_module("reference", NAME)
    _, config = stream_tiny.cell("float32")
    m = dict(config["model"], positions=16)
    n, hop_dim = 40, 10
    rng = np.random.default_rng(0)
    hop = jnp.asarray(rng.normal(size=(n, hop_dim)).astype(np.float32))
    key = jax.random.PRNGKey(11)
    model = stream.StreamRanker(mod.model_config(m))
    ids = jnp.zeros((2,), jnp.int32)
    params = model.init(key, hop, None, ids, ids)["params"]
    theirs = R.init_params(key, m, hop_dim, n)
    from benchmark import check

    ours = check.flatten(jax.tree_util.tree_map(np.asarray, dict(params)))
    assert set(ours) == set(theirs)
    for k in ours:
        np.testing.assert_array_equal(ours[k], np.asarray(theirs[k]), err_msg=k)
    src = rng.integers(0, n, 32).astype(np.int32)
    dst = np.repeat([3, 4, 5, 6, 7, 8], [5, 11, 2, 6, 7, 1]).astype(np.int32)
    y = rng.normal(15, 1, 32).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        qef = stream.previous_target(jnp.asarray(dst), jnp.asarray(y), 16)
        got = model.apply({"params": params}, hop, None, jnp.asarray(src), jnp.asarray(dst), qef)
        prev = R.previous_targets(dst.reshape(2, 16), y.reshape(2, 16), m)
        want = np.concatenate([
            R.row_predictions(theirs, R.standard_table(hop), src.reshape(2, 16)[r], dst.reshape(2, 16)[r], jnp.asarray(prev[r]), m, "f32")
            for r in range(2)
        ])
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


# -- the rehearsal ------------------------------------------------------------------------------


def _measure(trace=False, seconds=0.5):
    cell, config = stream_tiny.cell()
    return run.measure(cell, config, SEED, seconds, trace, jax.devices()[:1])


def test_sound_run_is_correct_and_counts_whole_units():
    got = _measure()
    assert got["correct"], got["compared"]
    window = got["run"].window
    assert window.steps == window.launches * window.extras["steps_per_launch"] > 0
    assert window.records == window.steps * 256
    assert "stream/gdn/scan" in window.extras["program_text"]
    assert run.load_module("metrics", "records_count_gap").read(got["run"]) == 0


def _parameters_left(real):
    """Adam's moments advance and the update is never applied: the
    dispatch's two steps both take their gradient at the first weights
    (the warm-up's first rate is 0), so only ``change_gap`` can see it."""
    def step(state, *args):
        new, loss = real(state, *args)
        return new.replace(params=state.params), loss
    return step


# The faults ``test_rehearsal.py`` plants in every cell, and one more.
FAULTS = dict(
    {k: rehearsal.FAULTS[k] for k in ("state_unchanged", "half_batch_left_out")},
    moments_advance_parameters_left=_parameters_left,
)


@pytest.mark.parametrize("fault", [FAULTS[k] for k in sorted(FAULTS)], ids=sorted(FAULTS))
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    from dragonfly2_tpu.trainer import online_graph, train

    broken = fault(train._graph_train_step)
    monkeypatch.setattr(train, "_graph_train_step", broken)
    monkeypatch.setattr(online_graph, "_graph_train_step", broken)
    got = _measure()
    assert not got["correct"], got["compared"]
    if fault is _parameters_left:
        over = {r["name"] for r in got["compared"] if r["value"] > r["limit"]}
        assert over == {"change_gap"}, got["compared"]


@pytest.mark.parametrize("variant", ["fp8", "half_batch"])
def test_control_and_fault_in_the_reference_are_not_correct(variant):
    cell, config = stream_tiny.cell()
    got = controls.readings(cell, config, SEED, [variant], jax.devices()[:1])[variant]
    failed = [k for k, v in got.items() if k in cell["limits"] and v > cell["limits"][k]]
    assert failed, got


def test_traced_run_reads_the_counters_and_spans_and_no_device_scope():
    """A CPU trace has no device plane: the scope readers say nothing, the
    counters and span attributes read."""
    got = _measure(trace=True)
    r = got["run"]
    r.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert not r.trace.devices
    for name in ("gdn_scan_share", "attn_core_share", "moe_share", "moe_experts_roofline", "step_mfu"):
        assert run.load_module("metrics", name).read(r) is None
    ratio = run.load_module("metrics", "moe_load_max_over_mean").read(r)
    assert ratio is not None and ratio >= 1.0
    # 4 experts of 16 held: 25 under an even router, which 32 hidden dims do not make
    assert 5 < run.load_module("metrics", "moe_slots_held_share").read(r) < 60
    assert run.load_module("metrics", "compiles_in_window").read(r) == 0


def test_scope_reader_sums_device_time_by_scope():
    """The reduction itself, on a made-up device line and program text."""
    from types import SimpleNamespace

    from benchmark.reduce import stream_scopes, xplane

    text = "\n".join([
        '  %fusion.1 = f32[8]{0} fusion(%a), metadata={op_name="jit(f)/while/body/jvp(StreamRanker)/stream/gdn/scan/dot_general"}',
        '  %fusion.2 = f32[8]{0} fusion(%a), metadata={op_name="jit(f)/transpose(jvp(StreamRanker))/checkpoint/rematted_computation/stream/moe/experts/ragged_dot"}',
        '  %fusion.3 = f32[8]{0} fusion(%a), metadata={op_name="jit(f)/optimizer/add"}',
        '  %while.4 = f32[8]{0} while(%a), metadata={op_name="jit(f)/jvp(StreamRanker)/stream/gdn/scan/while"}',
    ])
    ops = [
        (0.0, 4.0, "%while.4 = f32[8]{0} while(%a)"), (0.0, 1.0, "%fusion.1 = f32[8]{0} fusion(%a)"),
        (2.0, 3.0, "%fusion.1 = f32[8]{0} fusion(%a)"), (5.0, 6.0, "%fusion.2 = f32[8]{0} fusion(%a)"),
        (6.0, 8.0, "%fusion.3 = f32[8]{0} fusion(%a)"),
    ]
    trace = xplane.Trace(devices=[xplane.Device("/device:TPU:0", ops=ops)], spans=[])
    r = SimpleNamespace(trace=trace, window=SimpleNamespace(extras={"program_text": text}))
    got = stream_scopes.seconds(r)
    assert got == {"gdn/scan": 4.0, "moe/experts": 1.0, "busy": 7.0}
    assert stream_scopes.share(r, ["gdn/scan"]) == pytest.approx(100 * 4 / 7)
    assert stream_scopes.share(r, stream_scopes.MOE) == pytest.approx(100 / 7)
    assert stream_scopes.scope_of("jit(f)/hop/src/dot") is None
