"""The trainer's own tracing (DESIGN.md §21, PR 25): spans around the
phases of ``OnlineGraphTrainer.run()`` and ``_train_graph_model``, on the
profiler's clock too; the ledger of what the device has finished, counted
by the train step itself; scope names inside the step; which call
compiled.  Tiny sizes on the CPU: counts, names and parentage, never a
time."""

import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dragonfly2_tpu.models.gnn import GATRanker, GNNConfig, build_neighbor_table
from dragonfly2_tpu.models.hop import HopConfig, HopRanker
from dragonfly2_tpu.trainer import metrics as trainer_metrics
from dragonfly2_tpu.trainer import online_graph, train
from dragonfly2_tpu.utils import compile_cache, dfspan, tracing
from test_online_graph import _downloads, _mk_cluster, _mk_trainer, _topo

SUPER_STEPS, BATCH = 4, 256
PER_DISPATCH = SUPER_STEPS * BATCH


@pytest.fixture
def ring():
    """A fresh in-memory ring on the default tracer, tracing on."""
    old_exporter, old_enabled = tracing.default_tracer.exporter, tracing.enabled()
    tracing.default_tracer.exporter = tracing.InMemoryExporter()
    tracing.set_enabled(True)
    try:
        yield tracing.default_tracer.exporter
    finally:
        tracing.default_tracer.exporter = old_exporter
        tracing.set_enabled(old_enabled)


def _fed_trainer(dispatches, tmp_path=None, **cfg):
    cluster = _mk_cluster()
    tr = _mk_trainer(cluster, tmp_path, **cfg)
    tr.feed_downloads(*_downloads(cluster, 2, dispatches * PER_DISPATCH))
    return tr


def _run_tree(ring):
    root = ring.find("trainer/run")[-1]
    spans = ring.trace(root.trace_id)
    kids = lambda parent: [s for s in spans if s.parent_id == parent.span_id]
    return root, spans, kids


class TestRunSpans:
    def test_tree_of_one_run(self, ring):
        tr = _fed_trainer(3)
        assert tr.run(max_dispatches=3, idle_timeout=0.1) == 3
        tr.close()
        root, spans, kids = _run_tree(ring)
        assert root.parent_id is None and spans[-1] is root     # opened first, closed last
        assert [s.name for s in kids(root)] == ["trainer/next_block", "trainer/dispatch"] * 3
        for i, d in enumerate(s for s in kids(root) if s.name == "trainer/dispatch"):
            assert d.attributes["dispatch"] == i and d.attributes["records"] == PER_DISPATCH
            assert [s.name for s in kids(d)] == [
                "trainer/recycle", "trainer/h2d", "trainer/enqueue"
            ]
        blocks = [s for s in kids(root) if s.name == "trainer/next_block"]
        assert [b.attributes["records"] for b in blocks] == [PER_DISPATCH] * 3
        # The whole stream was fed in one piece before run(): one queue
        # item for the first block, the leftover for the next two.
        assert [b.attributes["items"] for b in blocks] == [1, 0, 0]
        assert all(b.attributes["wait_s"] >= 0.0 for b in blocks)
        a = root.attributes
        assert (a["dispatches"], a["records_enqueued"]) == (3, 3 * PER_DISPATCH)
        assert 1 <= a["in_flight_max"] <= 3 and a["compiles"] >= 0
        assert "moe_row_mover" not in a and "gdn_scan_carrier" not in a    # the hop ranker has no run_attrs
        assert 0 <= a["records_trained"] <= a["records_enqueued"]
        assert 0 <= ring.self_ns(root) <= root.end_ns - root.start_ns

    def test_a_run_that_ends_starved_closes_its_last_wait(self, ring):
        tr = _fed_trainer(1)
        assert tr.run(idle_timeout=0.05) == 1
        root, _, kids = _run_tree(ring)
        last = kids(root)[-1]
        assert last.name == "trainer/next_block" and last.attributes["records"] == 0
        assert last.attributes["wait_s"] >= 0.05

    def test_refresh_and_checkpoint_say_how_far_ahead_the_host_was(self, ring, tmp_path):
        cluster = _mk_cluster()
        tr = _fed_trainer(2, tmp_path, refresh_every=1, checkpoint_every=2)
        tr.feed_topology(*_topo(cluster, seed=7))
        assert tr.run(max_dispatches=2, idle_timeout=0.1) == 2
        root, spans, kids = _run_tree(ring)
        refresh = [s for s in kids(root) if s.name == "trainer/refresh"]
        assert len(refresh) == 2
        assert refresh[0].attributes["probe_edges"] > 0 and "probe_edges" not in refresh[1].attributes
        (ckpt,) = [s for s in kids(root) if s.name == "trainer/checkpoint"]
        assert ckpt.attributes["bytes"] > 0
        assert all(0 <= s.attributes["in_flight"] <= 2 for s in refresh + [ckpt])

    def test_tracing_off_changes_nothing_the_trainer_computes(self, ring):
        def one(on):
            tracing.set_enabled(on)
            tr = _fed_trainer(3)
            tr.run(max_dispatches=3, idle_timeout=0.1)
            tr.close()
            return online_graph.state_hash(tr.state), float(tr.last_loss), tr.records_trained

        on = one(True)
        spans_on = len(ring.spans)
        off = one(False)
        assert on == off
        assert len(ring.spans) == spans_on      # and off records nothing

    def test_dispatch_is_on_the_host_plane_of_a_profile(self, ring, tmp_path):
        tr = _fed_trainer(2)
        tr.run(max_dispatches=1, idle_timeout=0.1)     # compile outside the profile
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            tr.run(max_dispatches=1, idle_timeout=0.1)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(path)
        lines = [
            {ev.name: (ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events}
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines
        ]
        (line,) = [names for names in lines if "trainer/run" in names]
        run = line["trainer/run"]
        for name in ("trainer/next_block", "trainer/dispatch", "trainer/h2d", "trainer/enqueue"):
            lo, hi = line[name]
            assert run[0] <= lo <= hi <= run[1], name
        # The ring's record of the same span: another clock's zero, the
        # same length to well under the readers' 5 ms.
        root = ring.find("trainer/run")[-1]
        assert abs((run[1] - run[0]) - (root.end_ns - root.start_ns)) < 5e6


def test_a_process_without_jax_imports_none_for_a_span():
    code = (
        "import sys\n"
        "from dragonfly2_tpu.utils import tracing\n"
        "with tracing.default_tracer.span('daemon/piece'):\n"
        "    pass\n"
        "assert tracing.default_tracer.exporter.find('daemon/piece')\n"
        "assert 'jax' not in sys.modules\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    subprocess.run([sys.executable, "-c", code], cwd=root, check=True, timeout=120)


class TestRingHelpers:
    def test_spans_of_one_trace_and_self_time(self):
        ring = tracing.InMemoryExporter()
        mk = lambda name, trace, sid, parent, lo, hi: tracing.Span(
            name, trace, sid, parent, start_ns=lo, end_ns=hi
        )
        root = mk("root", "t1", "a", None, 0, 100)
        for s in (
            mk("kid", "t1", "b", "a", 10, 30),
            mk("grandkid", "t1", "c", "b", 12, 20),
            mk("kid", "t1", "d", "a", 25, 50),          # overlaps the first by 5
            mk("late", "t1", "e", "a", 90, 140),        # runs past its parent
            mk("other", "t2", "f", "a", 0, 100),        # another trace
            root,
        ):
            ring.export(s)
        assert [s.span_id for s in ring.trace("t1")] == ["b", "c", "d", "e", "a"]
        assert ring.self_ns(root) == 100 - (20 + 20 + 10)
        assert ring.self_ns(ring.trace("t1")[1]) == 8     # a leaf keeps its duration


# -- the ledger ---------------------------------------------------------------

def _faults():
    from benchmark.tests.test_rehearsal import FAULTS

    return FAULTS


@pytest.mark.parametrize(
    "fault,share", [(None, 1.0), ("half_batch_left_out", 0.5), ("state_unchanged", 0.0)]
)
def test_records_trained_is_what_the_step_consumed(fault, share, monkeypatch, ring):
    if fault is not None:
        broken = _faults()[fault](train._graph_train_step)
        monkeypatch.setattr(online_graph, "_graph_train_step", broken)
    enqueued = trainer_metrics.ONLINE_RECORDS_ENQUEUED.value()
    trained = trainer_metrics.ONLINE_RECORDS_TRAINED.value()
    tr = _fed_trainer(3)
    assert tr.run(max_dispatches=3, idle_timeout=0.1) == 3
    jax.block_until_ready(tr.last_loss)                 # the drain
    tr.close()
    want = int(3 * PER_DISPATCH * share)
    assert tr.records_seen == 3 * PER_DISPATCH
    assert tr.records_trained == want
    assert tr.dispatches_in_flight == 0 and tr.dispatches_completed == tr.dispatch == 3
    assert 1 <= tr.dispatches_in_flight_max <= 3
    assert trainer_metrics.ONLINE_RECORDS_ENQUEUED.value() - enqueued == 3 * PER_DISPATCH
    assert trainer_metrics.ONLINE_RECORDS_TRAINED.value() - trained == want
    assert trainer_metrics.ONLINE_DISPATCHES_IN_FLIGHT.value() == 0


def test_in_flight_falls_to_zero_without_a_close(ring):
    tr = _fed_trainer(2)
    tr.run(max_dispatches=2, idle_timeout=0.1)
    jax.block_until_ready(tr.last_loss)
    tr.feed_downloads(*_downloads(_mk_cluster(), 3, 10))    # not a block: run() only sweeps
    assert tr.run(idle_timeout=0.05) == 0
    assert tr.dispatches_in_flight == 0 and tr.records_trained == 2 * PER_DISPATCH


def test_rows_wrap_and_a_dispatch_still_counts_its_own(ring):
    tr = _fed_trainer(1)
    tr.state = tr.state.replace(rows=jnp.asarray(2**32 - 100, jnp.uint32))
    tr.run(max_dispatches=1, idle_timeout=0.1)
    tr.close()
    assert tr.records_trained == PER_DISPATCH
    assert int(tr.state.rows) == PER_DISPATCH - 100


def test_rows_stay_out_of_the_checkpoint(tmp_path, ring):
    tr = _fed_trainer(1, tmp_path)
    tr.run(max_dispatches=1, idle_timeout=0.1)
    assert "rows" not in tr._payload()
    tr.checkpoint()
    back = _fed_trainer(0, tmp_path)
    assert back.resume()
    assert (back.records_trained, back.dispatches_completed) == (PER_DISPATCH, 1)
    assert back.dispatches_in_flight == 0 and int(back.state.rows) == 0


def test_the_job_carries_the_exact_count(ring):
    cluster = _mk_cluster()
    src, dst, rtt = _topo(cluster, seed=1)
    table = build_neighbor_table(128, src, dst, rtt, max_neighbors=8)
    es, ed, y = _downloads(cluster, 5, 1000)
    state, _, history = train.train_hop_ranker(
        cluster._host_feature_matrix(), table, es, ed, y,
        model_config=HopConfig(hidden=16, out_dim=8, node_embed_dim=4),
        config=train.TrainConfig(epochs=2, log_every=1), batch_size=128,
    )
    steps = 2 * (900 // 128)
    job = ring.find("train/job")[-1]
    assert (job.attributes["records_trained"], job.attributes["steps"]) == (steps * 128, steps)
    assert int(state.rows) == steps * 128 and len(history) == steps
    spans = ring.trace(job.trace_id)
    names = [s.name for s in spans if s.parent_id == job.span_id]
    assert names == (
        ["train/shuffle", "train/init"]
        + (["train/shuffle"] + ["train/batch", "train/step", "train/step_sync"] * (steps // 2)) * 2
        + ["train/validate"]
    )
    assert [s.attributes["step"] for s in spans if s.name == "train/step"] == list(range(steps))


# -- names inside the step ----------------------------------------------------

HOP_SCOPES = ("hop/gather", "hop/src", "hop/dst", "hop/pair", "hop/head", "loss", "optimizer")
GAT_SCOPES = ("gat/gather", "gat/attention", "gat/aggregate", "gat/head", "loss", "optimizer")


def _lowered_step(model, feat_dim):
    n, k, b = 64, 4, 32
    nf = jnp.zeros((n, feat_dim), jnp.float32)
    table = build_neighbor_table(
        n, np.arange(n), (np.arange(n) + 1) % n, np.ones(n, np.float32), max_neighbors=k
    )
    ids = jnp.zeros((b,), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), nf, table, ids, ids)["params"]
    state = train.TrainState.create(
        apply_fn=model.apply, params=params,
        tx=train._make_optimizer(train.TrainConfig(), 10),
        dropout_rng=jax.random.PRNGKey(1),
    )
    step = jax.jit(lambda s, f, t, a, c, y: train._graph_train_step(s, f, t, a, c, y, None))
    return params, step.lower(state, nf, table, ids, ids, jnp.zeros((b,), jnp.float32))


@pytest.mark.parametrize(
    "model,feat_dim,scopes,modules",
    [
        (HopRanker(HopConfig(hidden=16, out_dim=8, node_embed_dim=4)), 12 * 5 + 2, HOP_SCOPES,
         {"HopEncoder_0", "Dense_0", "Dense_1", "Dense_2"}),
        (GATRanker(GNNConfig(hidden=16, out_dim=8, node_embed_dim=4)), 12, GAT_SCOPES,
         {"NodeEmbedding_0", "GATLayer_0", "GATLayer_1", "Dense_0", "Dense_1", "Dense_2", "Dense_3"}),
    ],
    ids=["hop", "gat"],
)
def test_every_scope_is_in_the_lowered_step_and_no_module_was_renamed(model, feat_dim, scopes, modules):
    params, lowered = _lowered_step(model, feat_dim)
    text = lowered.as_text(debug_info=True)
    for scope in scopes:
        assert f"/{scope}/" in text or f"({scope})" in text, scope
    # Forward and backward both carry a scope (under the module that holds it).
    name, first = type(model).__name__, scopes[1]
    assert re.search(rf'/jvp\({name}\)/(\w+/)?{first}/', text)
    assert re.search(rf'/transpose\(jvp\({name}\)\)/(\w+/)?{first}/', text)
    assert set(params) == modules


def test_the_dispatch_program_text_names_its_instructions(ring):
    tr = _fed_trainer(0)
    text = tr.dispatch_program_text()
    assert "_train_dispatch" in text
    for scope in HOP_SCOPES:
        assert f"/{scope}/" in text or f"({scope})" in text, scope
    assert not ring.find("trainer/run")        # asked for, never run


# -- which call compiled ------------------------------------------------------

def test_a_compile_lands_on_the_dispatch_that_paid_for_it(ring):
    compile_cache._install_compile_listener()
    total = compile_cache.XLA_COMPILES.value()
    tr = _fed_trainer(2)
    tr._ensure_snapshot()                      # the snapshot's programs are not the dispatch's
    tr.apply_pending_recycles()
    before = compile_cache.XLA_COMPILES.value()
    assert before >= total
    assert tr.run(max_dispatches=2, idle_timeout=0.1) == 2
    root, spans, kids = _run_tree(ring)
    first, second = [s for s in kids(root) if s.name == "trainer/dispatch"]
    assert first.attributes["compiles"] == 1 and first.attributes["compile_s"] > 0
    assert "compiles" not in second.attributes
    (enqueue,) = [s for s in kids(first) if s.name == "trainer/enqueue"]
    assert enqueue.attributes["compiles"] == 1          # the innermost span names the call
    assert all("compiles" not in s.attributes for s in kids(first) if s is not enqueue)
    assert root.attributes["compiles"] == 1
    assert compile_cache.XLA_COMPILES.value() - before == 1


def test_a_program_loaded_from_the_cache_is_not_a_compile(ring):
    compile_cache._install_compile_listener()
    before = compile_cache.XLA_COMPILES.value()
    with tracing.default_tracer.span("t/load") as span:
        compile_cache._on_event(compile_cache._CACHE_HIT)
        compile_cache._on_duration(compile_cache._BACKEND_COMPILE, 0.5)
        assert "compiles" not in span.attributes
        compile_cache._on_duration(compile_cache._BACKEND_COMPILE, 0.25)
        compile_cache._on_duration("/jax/core/compile/jaxpr_trace_duration", 9.0)
    assert span.attributes == {"compiles": 1, "compile_s": 0.25}
    assert compile_cache.XLA_COMPILES.value() - before == 1


# -- the witness pools its workers' observations ------------------------------

def test_span_witness_pools_what_the_workers_of_one_run_observed(tmp_path):
    pkg = os.path.dirname(os.path.abspath(dfspan.__file__))
    pkg = os.path.dirname(pkg)
    one, two = dfspan.SpanWitness(pkg), dfspan.SpanWitness(pkg)

    class Frame:
        def __init__(self, rel):
            self.f_code = type("C", (), {"co_filename": os.path.join(pkg, rel)})

    one.note(Frame("trainer/train.py"), "train/job", "span")     # before sharing began
    one.share(str(tmp_path), "gw0")
    two.share(str(tmp_path), "gw1")
    one.note(Frame("trainer/train.py"), "train/job", "span")
    two.note(Frame("trainer/online_graph.py"), "trainer/run", "span")
    two.note(Frame("trainer/online_graph.py"), "trainer/run", "span")
    want = {
        "dragonfly2_tpu/trainer/train.py": {"train/job"},
        "dragonfly2_tpu/trainer/online_graph.py": {"trainer/run"},
    }
    assert one.names_by_module() == two.names_by_module() == want
    assert sorted(os.listdir(tmp_path)) == ["gw0.tsv", "gw1.tsv"]
    with open(tmp_path / "gw1.tsv") as f:
        assert len(f.readlines()) == 1          # a site is shared once
    assert dfspan.SpanWitness(pkg).names_by_module() == {}      # a process that shares nothing
