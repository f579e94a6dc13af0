"""The transfer-stream ranker's expert layer (``models/stream.py``: the
shares of the uncut layer, a forced router, the routed experts' gradient
under both carriers of ``ops/slot_rows.py``) against the plain float32
reference's layer, and the ranker through the online trainer's normal
path, at tiny widths on the CPU.  The rest of the model's arithmetic
against its references is in ``tests/test_stream_reference.py``.  Counts,
values and gradients, never a time."""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dragonfly2_tpu.models import HopConfig, build_ranker, require_servable, stream
from dragonfly2_tpu.ops import attention_bwd, delta_scan, grouped_matmul, slot_rows
from dragonfly2_tpu.trainer import metrics as trainer_metrics
from dragonfly2_tpu.trainer.online_graph import OnlineGraphConfig, OnlineGraphTrainer
from dragonfly2_tpu.trainer.train import TrainConfig
from dragonfly2_tpu.utils import tracing
from tests._stream_sizes import B, L, M, N, ROWS, _records, cfg, ref  # noqa: F401 — fixtures


# -- the expert layer's share ------------------------------------------------------------


def _expert_weights(seed, m):
    rng = np.random.default_rng(seed)
    d, e, f = m["hidden_size"], m["num_experts"], m["moe_intermediate_size"]
    w = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32) * 0.3)
    return {
        "router": w(d, e), "w_gate": w(e, d, f), "w_up": w(e, d, f), "w_down": w(e, f, d),
        "shared": {"w_gate": w(d, f), "w_up": w(d, f), "w_down": w(f, d)}, "shared_gate": w(d, 1),
    }


def _share(p, first, count):
    cut = lambda a: a[first:first + count]
    return {**p, "w_gate": cut(p["w_gate"]), "w_up": cut(p["w_up"]), "w_down": cut(p["w_down"])}


def test_the_shares_add_up_to_the_uncut_layer(ref, cfg):
    """Four chips hold four experts each of sixteen.  Their routed parts,
    with the shared expert (which every chip computes alike) counted once,
    are what the reference gives for the whole layer with all sixteen."""
    p = _expert_weights(5, M)
    x = jnp.asarray(np.random.default_rng(6).normal(size=(B, M["hidden_size"])).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(p, x, {**M, "experts_held_first": 0, "num_experts_held": 16}, "f32")
        s = p["shared"]
        shared = jax.nn.sigmoid(x @ p["shared_gate"]) * (
            (jax.nn.silu(x @ s["w_gate"]) * (x @ s["w_up"])) @ s["w_down"]
        )
        parts, slots = [], 0
        for first in range(0, 16, 4):
            share = dataclasses.replace(cfg, experts_held=(first, 4))
            y, sizes, _ = stream.expert_layer(_share(p, first, 4), x, share)
            parts.append(y - shared)
            slots += int(sizes.sum())
            # the reference's own share, the same share
            theirs = ref.expert_layer(_share(p, first, 4), x, {**M, "experts_held_first": first}, "f32")
            np.testing.assert_allclose(y, theirs, rtol=0, atol=2e-5)
    assert slots == M["num_experts_per_tok"] * B          # every slot lives on exactly one chip
    np.testing.assert_allclose(sum(parts) + shared, whole, rtol=0, atol=5e-5)


@pytest.fixture(params=[slot_rows.XLA, slot_rows.KERNEL])
def carrier(request, monkeypatch):
    """The expert layer's slot rows moved by each of ``ops/slot_rows.py``'s
    carriers: XLA's operations as tier-1's backend picks them, and the
    kernels (interpreted off the chip) as a TPU would."""
    if request.param == slot_rows.KERNEL:
        monkeypatch.setattr(slot_rows, "row_mover", lambda width, dtype: slot_rows.KERNEL)
    return request.param


@pytest.mark.parametrize("held,k,taken", [((0, 1), 3, 1), ((0, 4), 3, 3), ((8, 4), 3, 0), ((0, 8), 6, 6)],
                         ids=["one-expert-takes-every-token", "every-slot-held", "none-held",
                              "six-held-slots-a-token"])
def test_no_slot_is_dropped_under_a_forced_router(ref, cfg, held, k, taken, carrier):
    """Every token sends its ``k`` slots to experts 0 .. k-1.  Held or
    absent, each slot is counted once; a held expert computes every token
    (blocks of B slots: three when three slots of every token are held,
    six when six are), and the result is the reference's, whose loop over
    the held experts has no notion of capacity."""
    m = {**M, "num_experts_per_tok": k}
    p = _expert_weights(7, m)
    d = m["hidden_size"]
    x = jnp.asarray(np.abs(np.random.default_rng(8).normal(size=(B, d))).astype(np.float32) + 0.1)
    forced = np.full((d, 16), -1.0, np.float32)
    forced[:, :k] = [np.arange(k, 0, -1.0)]
    p = {**p, "router": jnp.asarray(forced)}
    first, count = held
    share = dataclasses.replace(cfg, experts_held=held, num_experts_per_tok=k)
    with jax.default_matmul_precision("highest"):
        y, sizes, _ = jax.jit(lambda p, x: stream.expert_layer(p, x, share))(_share(p, first, count), x)
        want = ref.expert_layer(
            _share(p, first, count), x, {**m, "experts_held_first": first, "num_experts_held": count}, "f32"
        )
    slots_held = int(sizes.sum())
    top = np.argsort(-np.asarray(x @ p["router"]), axis=1)[:, :k]
    slots_absent = int(((top < first) | (top >= first + count)).sum())
    assert slots_held + slots_absent == k * B
    assert slots_held == taken * B
    assert [int(n) for n in sizes] == [B] * taken + [0] * (count - taken)
    np.testing.assert_allclose(y, want, rtol=0, atol=1e-4)


@pytest.mark.parametrize("blocks", [1, 2, 3], ids=["grown-as-filled", "one-of-padding", "two-of-padding"])
def test_routed_experts_gradient_equals_the_dense_reference(ref, cfg, blocks, carrier):
    """Whatever number of blocks the layer always runs: the rows past the
    held slots ride with the last expert at weight nought and add nothing,
    to the result or to any gradient."""
    cfg = dataclasses.replace(cfg, expert_blocks=blocks)
    p = _share(_expert_weights(11, M), 4, 4)
    x = jnp.asarray(np.random.default_rng(12).normal(size=(B, M["hidden_size"])).astype(np.float32))
    w = jnp.asarray(np.random.default_rng(13).normal(size=x.shape).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        ours = jax.grad(lambda p, x: (stream.expert_layer(p, x, cfg)[0] * w).sum(), argnums=(0, 1))(p, x)
        theirs = jax.grad(lambda p, x: (ref.expert_layer(p, x, M, "f32") * w).sum(), argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(ours), jax.tree_util.tree_leaves(theirs)):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * max(float(np.abs(b).max()), 1.0))


# -- a block's backward by hand against autodiff's --------------------------------------

_T, _D, _F, _E = 96, 40, 16, 4
_BF16 = jnp.bfloat16
_GROUPS = {
    # (rows each held expert received, rows at weight nought riding the last)
    "even": ([24, 24, 24, 24], 0),
    "one-empty-group": ([40, 0, 30, 26], 0),
    "padded-tail-on-the-last-group": ([20, 12, 8, 56], 40),
    "every-row-in-the-last-group": ([0, 0, 0, 96], 0),
}


def _a_block(act, groups):
    per, padded = _GROUPS[groups]
    k = jax.random.split(jax.random.PRNGKey(len(act) + len(groups)), 6)
    w = lambda key, *s: (jax.random.normal(key, s) * 0.3).astype(_BF16)
    wb = jax.random.uniform(k[1], (_T,), minval=0.05).at[_T - padded:].set(0.0)
    dyb = jax.random.normal(k[5], (_T, _D), jnp.float32).at[_T - padded:].set(0.0)  # the mask by ``valid``
    return (
        jax.random.normal(k[0], (_T, _D)).astype(_BF16), wb, jnp.asarray(per, jnp.int32),
        w(k[2], _E, _D, _F), w(k[3], _E, _D, _F), w(k[4], _E, _F, _D), dyb,
    )


@pytest.mark.parametrize("products", [grouped_matmul.XLA, grouped_matmul.KERNEL])
@pytest.mark.parametrize("groups", list(_GROUPS))
@pytest.mark.parametrize("act", ["relu", "silu", "relu2"])
def test_block_backward_by_hand_equals_autodiffs(act, groups, products, monkeypatch):
    """``_expert_block_bwd`` against ``jax.vjp(_expert_block)``, the oracle
    (its products by ``ragged_dot``): the five gradients (four where the
    expert has no gate, ``relu2``) in autodiff's dtypes; ``dwb`` (float32
    on both sides, by another sum: over F columns and not over D) to 1e-5,
    those that autodiff rounds to bfloat16 to that rounding (``dxb``'s two
    halves are summed before the rounding here and after it there).  With
    its products by ``ops/grouped_matmul.py``'s kernels (interpreted off
    the chip) it is handed the cotangent as the layer hands it over on the
    chip, in the bfloat16 it was gathered in."""
    xb, wb, per, w_gate, w_up, w_down, dyb = _a_block(act, groups)
    if products == grouped_matmul.KERNEL:
        dyb = dyb.astype(_BF16)
    weights = (w_up, w_down) if act in stream.UNGATED else (w_gate, w_up, w_down)
    _, pull = jax.vjp(
        lambda xb, wb, *ws: stream._expert_block(xb, wb, per, ws, _BF16, act), xb, wb, *weights,
    )
    want = pull(dyb.astype(jnp.float32))
    monkeypatch.setattr(grouped_matmul, "grouped_carrier", lambda *a: products)
    got = stream._expert_block_bwd(
        xb, wb, per, weights[:-1], *stream._transposed(weights), dyb, _BF16, act
    )
    names = ("dxb", "dwb") + tuple("dW" + n[1:] for n in stream.expert_matrices(act))
    assert len(got) == len(want) == len(names) == len(weights) + 2
    ulp = float(jnp.finfo(_BF16).eps)                      # 2^-7: one step of bfloat16 at 1
    for name, a, b in zip(names, got, want):
        assert (a.dtype, a.shape) == (b.dtype, b.shape), name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(b).max() > 0, name
        rel = 1e-5 if name == "dwb" else ulp
        np.testing.assert_allclose(a, b, rtol=0, atol=rel * float(np.abs(b).max()), err_msg=name)


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("act", ["relu", "silu", "relu2"])
def test_backward_loop_holds_seven_grouped_products_and_no_wide_row_sum(act):
    """The body of ``_routed_bwd``'s loop over blocks: seven
    ``ragged_dot_general`` where ``jax.vjp(_expert_block)`` put nine (five
    for six where the expert has no gate, ``relu2``), the down product's
    forward (``[T, F] x [E, F, D]``) not among them, and no ``reduce_sum``
    over a ``[T, D]`` operand (``dwb`` sums over F)."""
    xb, _, per, w_gate, w_up, w_down, dyb = _a_block(act, "one-empty-group")
    gated = act not in stream.UNGATED
    k = 3
    w_sorted = jax.random.uniform(jax.random.PRNGKey(2), (k * _T,))
    tok_sorted = jnp.arange(k * _T, dtype=jnp.int32) // k
    res = (xb, w_sorted, tok_sorted, per, (w_gate, w_up, w_down) if gated else (w_up, w_down))
    whole = jax.make_jaxpr(lambda res, dy: stream._routed_bwd(_BF16, 2, act, res, dy))(res, dyb.astype(_BF16))
    (loop,) = [e for e in _equations(whole.jaxpr) if e.primitive.name == "while"]
    eqns = list(_equations(loop.params["body_jaxpr"].jaxpr))
    products = [e for e in eqns if e.primitive.name == "ragged_dot_general"]
    assert len(products) == (7 if gated else 5)
    shapes = [tuple(v.aval.shape for v in e.invars[:2]) for e in products]
    if gated:
        assert ((_T, _F), (_E, _F, _D)) not in shapes           # h . W_down: the forward's alone
        assert shapes.count(((_T, 2 * _F), (_E, 2 * _F, _D))) == 1   # dxb, once, over [dG | dU]
        assert shapes.count(((_T, _D), (_E, _D, _F))) == 3      # gate and up again, and dh
    else:
        assert shapes.count(((_T, _F), (_E, _F, _D))) == 1      # dxb alone: not h . W_down
        assert shapes.count(((_T, _D), (_E, _D, _F))) == 2      # up again, and dh
    sums = [e for e in eqns if e.primitive.name == "reduce_sum"]
    assert sums and all(e.invars[0].aval.shape != (_T, _D) for e in sums)
    assert any(e.invars[0].aval.shape == (_T, _F) for e in sums)


# -- through the trainer's normal path ----------------------------------------------------


@pytest.fixture
def ring():
    old_exporter, old_enabled = tracing.default_tracer.exporter, tracing.enabled()
    tracing.default_tracer.exporter = tracing.InMemoryExporter()
    tracing.set_enabled(True)
    try:
        yield tracing.default_tracer.exporter
    finally:
        tracing.default_tracer.exporter = old_exporter
        tracing.set_enabled(old_enabled)


def _trainer(cfg, tmp=None, **kw):
    rng = np.random.default_rng(0)
    topo = (rng.integers(0, N, 200).astype(np.int32), rng.integers(0, N, 200).astype(np.int32),
            rng.random(200).astype(np.float32))
    return OnlineGraphTrainer(
        OnlineGraphConfig(num_nodes=N, max_neighbors=4, batch_size=B, super_steps=2, model=cfg,
                          queue_capacity=8, train=TrainConfig(seed=2), **kw),
        node_feats=rng.normal(size=(N, 2)).astype(np.float32),
        topo_src=topo[0], topo_dst=topo[1], topo_rtt=topo[2], checkpoint_dir=tmp,
    )


def _feed(tr, dispatches, seed=0):
    for i in range(dispatches):
        parts = [_records(seed + 10 * i + s) for s in range(2)]
        tr.feed_downloads(*(np.concatenate(p) for p in zip(*parts)))
    tr.end_of_stream()


def test_run_counts_every_record_and_every_slot(cfg, ring):
    """``records_count_gap`` 0 through ``run()``: the same feed, dispatch
    and ledger as the hop ranker's; the expert layers' count arrives with
    the ledger, on the counters and on the dispatch's span."""
    routed0, held0 = trainer_metrics.MOE_SLOTS_ROUTED.value(), trainer_metrics.MOE_SLOTS_HELD.value()
    enq0, done0 = trainer_metrics.ONLINE_RECORDS_ENQUEUED.value(), trainer_metrics.ONLINE_RECORDS_TRAINED.value()
    tr = _trainer(cfg)
    _feed(tr, 3)
    assert tr.run(idle_timeout=5.0) == 3
    tr.close()
    assert tr.records_trained == tr.records_seen == 3 * 2 * B
    assert trainer_metrics.ONLINE_RECORDS_ENQUEUED.value() - enq0 == 3 * 2 * B
    assert trainer_metrics.ONLINE_RECORDS_TRAINED.value() - done0 == 3 * 2 * B       # the gap is 0
    routed = trainer_metrics.MOE_SLOTS_ROUTED.value() - routed0
    held = trainer_metrics.MOE_SLOTS_HELD.value() - held0
    assert routed == 3 * 2 * B * cfg.num_experts_per_tok * cfg.num_hidden_layers
    assert 0 < held < routed
    spans = ring.find("trainer/dispatch")
    assert len(spans) == 3
    per = cfg.num_hidden_layers * cfg.experts_held[1]
    assert sum(s.attributes["moe_load_mean"] * per for s in spans) == pytest.approx(held)
    assert all(s.attributes["moe_load_max"] >= s.attributes["moe_load_mean"] > 0 for s in spans)
    assert sum(s.attributes["moe_slots_held"] for s in spans) == held
    assert sum(s.attributes["moe_slots_routed"] for s in spans) == routed
    # One attention layer, without a window: its block pairs are the full
    # kind's alone, 10 a row in the band by position (4 blocks of 8), and
    # the segments of the packed rows cut some of them.
    for s in spans:
        assert {k for k in s.attributes if k.startswith("attn_pairs_")} == {"attn_pairs_run_full", "attn_pairs_in_band_full"}
        assert 2 * ROWS * 4 <= s.attributes["attn_pairs_run_full"] < s.attributes["attn_pairs_in_band_full"] == 2 * ROWS * 10
    # Which carrier moved the expert layers' slot rows and which carried
    # the delta rule's state: tier-1 runs on the CPU, where jnp.take,
    # .at[].add and a lax.scan do (the kernels on a TPU).
    (root,) = ring.find("trainer/run")
    assert root.attributes["moe_row_mover"] == slot_rows.XLA
    assert root.attributes["gdn_scan_carrier"] == delta_scan.XLA
    assert root.attributes["moe_grouped_carrier"] == grouped_matmul.XLA
    assert np.isfinite(float(tr.last_loss))
    src, dst, y = _records(99)
    assert np.isfinite(tr.eval_mae(src, dst, y))


def test_the_run_span_is_told_both_carriers_by_the_tests_the_step_makes(cfg, monkeypatch):
    """``trainer/run``'s carriers (``moe_row_mover``, ``gdn_scan_carrier``,
    ``moe_grouped_carrier``, ``attn_carrier``) come from
    ``build_ranker(...).run_attrs``: on the CPU each says ``xla``; on a
    TPU the published configuration (bfloat16, heads 128 x 128, chunks of
    64, attention heads of 256 in blocks of 512) says ``kernel`` for
    each, and tier-1's float32 one keeps the ``lax.scan`` while its
    float32 rows move by the kernels."""
    published = stream.StreamRankerConfig()
    both = lambda c: build_ranker(c).run_attrs()
    layers = {"layers_attention": 1, "layers_deltanet": 3, "layers_experts": 4}
    assert both(cfg) == both(published) == {
        "moe_row_mover": slot_rows.XLA, "moe_grouped_carrier": grouped_matmul.XLA, "gdn_scan_carrier": delta_scan.XLA,
        "attn_carrier": attention_bwd.XLA, **layers,
    }
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert both(published) == {
        "moe_row_mover": slot_rows.KERNEL, "moe_grouped_carrier": grouped_matmul.KERNEL,
        "gdn_scan_carrier": delta_scan.KERNEL, "attn_carrier": attention_bwd.KERNEL, **layers,
    }
    assert both(cfg)["gdn_scan_carrier"] == delta_scan.XLA
    # tier-1's widths are no lane groups: its products stay ragged_dot's
    assert both(cfg)["moe_grouped_carrier"] == grouped_matmul.XLA
    assert build_ranker(HopConfig(hidden=16)).run_attrs is None


def test_checkpoint_and_resume_continue_byte_identically(cfg, tmp_path):
    from dragonfly2_tpu.trainer.online_graph import state_hash

    def go(resume_after):
        tr = _trainer(cfg, str(tmp_path / f"ckpt{resume_after}"), checkpoint_every=1)
        _feed(tr, 2)
        if resume_after:
            assert tr.run(max_dispatches=1, idle_timeout=5.0) == 1
            tr.close()
            tr2 = _trainer(cfg, str(tmp_path / f"ckpt{resume_after}"), checkpoint_every=1)
            assert tr2.resume()
            tr2._downloads = tr._downloads
            tr = tr2
        tr.run(idle_timeout=5.0)
        tr.close()
        return state_hash(tr.state), tr.dispatch

    assert go(0) == go(1)


def test_step_scopes_name_the_new_layers(cfg):
    """Every scope of DESIGN.md 21 is on some instruction of the compiled
    dispatch, and the benchmark's reader cuts an ``op_name`` down to it."""
    from benchmark.reduce import stream_scopes
    from benchmark.tools.program_trace import instruction_scopes

    tr = _trainer(cfg)
    text = tr.dispatch_program_text()
    tr.close()
    found = {stream_scopes.scope_of(name) for name in instruction_scopes(text).values()}
    assert found >= {
        "embed", "gdn/proj", "gdn/conv", "gdn/scan", "gdn/out", "attn/proj", "attn/core",
        "moe/router", "moe/dispatch", "moe/experts", "moe/shared", "moe/combine", "head",
    }
    names = set(instruction_scopes(text).values())
    assert any("/loss/" in n or n.endswith("/loss") or "(loss)" in n for n in names)
    assert any("optimizer" in n for n in names)


def test_lowered_for_a_tpu_the_row_kernels_sit_under_the_layers_scopes(cfg, monkeypatch):
    """Where the kernels carry the slot rows, their calls are named by the
    scopes ``moe_share`` sums: the gathers ``stream/moe/dispatch``, the
    add-into ``stream/moe/combine``, forward and backward, and every grouped
    product is a ``ragged_dot`` under ``stream/moe/experts/grouped``
    (what ``moe_products_share`` sums once ``dispatch_program_text()``
    has given the compiled calls their source's name back)."""
    from benchmark.reduce import stream_scopes

    monkeypatch.setattr(slot_rows, "row_mover", lambda width, dtype: slot_rows.KERNEL)
    monkeypatch.setattr(slot_rows, "_interpret", lambda: False)
    p = _share(_expert_weights(11, M), 4, 4)
    x = jnp.zeros((B, M["hidden_size"]), jnp.float32)
    loss = lambda p, x: stream.expert_layer(p, x, cfg)[0].sum()
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).trace(p, x).lower(
        lowering_platforms=("tpu",)
    ).as_text(debug_info=True)
    named = dict(re.findall(r"(#loc\d+) = loc\(\"([^\"]*)\"", text))
    calls, products = {}, []
    for line in text.splitlines():
        kernel = re.search(r'kernel_name = "(slot_rows_\w+)"', line)
        if kernel:
            where = named[re.search(r"loc\((#loc\d+)\)\s*$", line).group(1)]
            calls.setdefault(kernel.group(1), set()).add(
                (stream_scopes.scope_of(where), "transpose(" in where)
            )
        if "chlo.ragged_dot" in line:
            products.append(named[re.search(r"loc\((#loc\d+)\)\s*$", line).group(1)])
    assert calls == {
        "slot_rows_gather": {("moe/dispatch", False), ("moe/dispatch", True)},
        "slot_rows_add": {("moe/combine", False), ("moe/combine", True)},
    }
    # Three products a block forward, seven backward (_expert_block_bwd).
    assert sorted("transpose(" in where for where in products) == [False] * 3 + [True] * 7
    assert all("/stream/moe/experts/grouped/" in where for where in products), products


# -- what the trainer refuses, and what stays as it was ----------------------------------------


def test_ranker_is_built_from_the_configurations_type(cfg):
    assert type(build_ranker(HopConfig()).module).__name__ == "HopRanker"
    assert build_ranker(HopConfig()).query_feats is None
    got = build_ranker(cfg)
    assert type(got.module).__name__ == "StreamRanker" and got.batch_multiple == L and not got.servable
    with pytest.raises(TypeError, match="no ranker is built from a dict"):
        build_ranker({})


def test_batch_must_be_whole_rows(cfg):
    with pytest.raises(ValueError, match="not a multiple of the 32 records"):
        OnlineGraphTrainer(
            OnlineGraphConfig(num_nodes=N, batch_size=B + 1, model=cfg),
            node_feats=np.zeros((N, 2), np.float32), topo_src=np.zeros(0, np.int32),
            topo_dst=np.zeros(0, np.int32), topo_rtt=np.zeros(0, np.float32),
        )


@pytest.mark.parametrize("where", ["export", "hop job", "gat job", "id recycling"])
def test_serving_and_jobs_refuse_the_stream_ranker_with_one_error(cfg, where):
    from dragonfly2_tpu.trainer import export, train

    with pytest.raises(NotImplementedError, match="trains through OnlineGraphTrainer.run\\(\\) only"):
        if where == "export":
            export.export_gnn_scorer(build_ranker(cfg).module, {}, np.zeros((N, 2)), None, np.arange(N))
        elif where == "hop job":
            train.train_hop_ranker(None, None, None, None, None, model_config=cfg)
        elif where == "gat job":
            train.train_gat_ranker(None, None, None, None, None, model_config=cfg)
        else:
            _trainer(cfg, node_ttl=60.0)
    require_servable(HopConfig(), "anywhere")          # and no one else


def _normal_text(text: str) -> str:
    """A compiled program's text without what names the source's lines."""
    text = re.sub(r'\s*(stack_frame_id=\d+|source_file="[^"]*"|source_line=\d+)', "", text)
    return "\n".join(l for l in text.splitlines() if not re.match(r"^\s*\d+ ", l))


def test_hop_dispatch_program_is_what_it_was_before_the_stream_ranker():
    """The hop configuration's compiled dispatch, instruction by
    instruction and scope by scope, is the text the parent of PR 27
    compiled (tiny, on the CPU backend of this container's jax 0.9.0; the
    digest was taken from a checkout of that commit with this same code).
    A PR that means to change the hop step takes a new digest.  The text
    is the compiler's own: ``dispatch_program_text()`` restores the scopes
    of what XLA left unnamed (PR 36), on top of it and nowhere else
    (tests/test_program_scopes.py)."""
    nothing = np.zeros(0, np.int32)
    tr = OnlineGraphTrainer(
        OnlineGraphConfig(num_nodes=64, max_neighbors=4, batch_size=32, super_steps=2,
                          model=HopConfig(hidden=16)),
        node_feats=np.zeros((64, 12), np.float32), topo_src=nothing, topo_dst=nothing,
        topo_rtt=nothing.astype(np.float32),
    )
    text = _normal_text(tr.lower_dispatch().compile().as_text())
    tr.close()
    assert "hop/src" in text and "stream/" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "66f9296e38bdf093971d42ef4a2d0ea401934cd74386fa85a9d6315c6cd79ea7"
    )
