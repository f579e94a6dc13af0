"""Every device operation of the stream step named by a program scope
(PR 36): ``trainer/program_scopes.py`` restores in the compiled text what
the compiler left unnamed (the grouped products' custom calls, XLA's own
copies), the model scopes what it left outside every scope, and the two
readers ``moe_products_share`` and ``step_unscoped_share`` read the
result.  Texts and counts, never a time."""

import json
import os
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench
from dragonfly2_tpu.models import stream
from dragonfly2_tpu.trainer import program_scopes
from dragonfly2_tpu.trainer.online_graph import OnlineGraphConfig, OnlineGraphTrainer
from dragonfly2_tpu.trainer.train import TrainConfig
from tests._stream_sizes import B, M, N, cfg  # noqa: F401 — fixtures

DATA = os.path.join(os.path.dirname(__file__), "data")
# Cut from the compiled text of the 16k cell's train dispatch, compiled
# for a described v5e in this container (jax 0.9.0): the computations on
# the way from the entry to the four layers' expert loops, forward and
# backward, each loop body's grouped products (``ragged-dot-none.N``) and
# their group tables, and six of XLA's asynchronous copies with the
# operands they copy; Mosaic bodies and backend configs left out.  The
# sources are what ``source_products`` read from the same ``lower()``.
EXCERPT = os.path.join(DATA, "smallthinker_16k_v5e_dispatch_excerpt.hlo")
SOURCES = os.path.join(DATA, "smallthinker_16k_v5e_dispatch_sources.json")
GROUPED = "/stream/moe/experts/grouped/"
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _lines_by_name(text):
    out = {}
    for line in text.split("\n"):
        head = re.match(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ", line)
        if head:
            out[head.group(1)] = line
    return out


def _op_names(text):
    return {name: (m.group(1) if (m := _OP_NAME.search(line)) else None) for name, line in _lines_by_name(text).items()}


def _without_op_names(text):
    return re.sub(r', metadata=\{[^}]*\}', "", text)


@pytest.fixture(scope="module")
def excerpt():
    with open(EXCERPT) as f:
        text = f.read()
    with open(SOURCES) as f:
        sources = [program_scopes.SourceProduct(name, tuple(types), loop) for name, types, loop in json.load(f)]
    return text, sources


# -- the 16k dispatch's v5e text ------------------------------------------------------------------


def test_every_grouped_product_of_the_16k_dispatch_takes_its_sources_scope(excerpt):
    """40 products (4 layers x 3 forward, x 7 backward), each under
    ``stream/moe/experts/grouped`` and the loop it sits in, forward and
    backward told apart by that loop where their types agree; each group
    table by the products it feeds."""
    text, sources = excerpt
    before, after = _op_names(text), _op_names(program_scopes.restore(text, sources))
    products = [n for n in before if n.startswith("ragged-dot-none")]
    assert len(products) == len(sources) == 40
    assert {before[n] for n in products} == {"ragged-dot-none"}          # as the compiler left them
    assert all(GROUPED in after[n] for n in products), [after[n] for n in products]
    assert sorted("transpose(" in after[n] for n in products) == [False] * 12 + [True] * 28
    loops = {
        re.search(r"body=%([\w.\-]+)", line).group(1): _OP_NAME.search(line).group(1)
        for line in text.split("\n") if re.match(r"^\s*%while[.\d]* = ", line) and "op_name=" in line
    }
    bodies = {}
    for line in text.split("\n"):
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            body = line.split()[0].lstrip("%")
        head = re.match(r"^\s*%(ragged-dot-none[.\d]*) = ", line)
        if head:
            bodies[head.group(1)] = body
    for n in products:
        loop = loops[bodies[n]]
        assert after[n].startswith(loop.rpartition("/while")[0] + "/"), (after[n], loop)
        assert after[n].endswith("/ragged_dot_general")
    tables = [n for n in before if n.startswith("ragged-dot-metadata")]
    assert tables and all(GROUPED in after[n] for n in tables)


def test_each_copy_xla_made_takes_its_operands_scope(excerpt):
    text, sources = excerpt
    lines, after = _lines_by_name(text), _op_names(program_scopes.restore(text, sources))
    before = _op_names(text)
    copies = [n for n in lines if re.match(r"copy(-start|-done)?[.\d]*$", n) and before[n] is None]
    assert len(copies) >= 6
    for n in copies:
        operand = re.search(r"\(%([\w.\-]+)\)", lines[n]).group(1)
        assert after[n] is not None and after[n] == after[operand], (n, operand)
        assert "/stream/" in after[n]


def test_restoring_changes_nothing_but_op_names(excerpt):
    """Every other character of the text as the compiler wrote it; a line
    that had no metadata gains it in the form XLA writes it."""
    text, sources = excerpt
    restored = program_scopes.restore(text, sources)
    assert restored != text
    assert _without_op_names(restored) == _without_op_names(text)
    assert re.search(r'%copy-start[.\d]* = .*\), metadata=\{op_name="[^"]+"\}$', restored, re.M)


def test_a_product_whose_types_the_sources_do_not_account_for_keeps_the_compilers_name(excerpt):
    """The match is checked by count: with one source of a type missing,
    no product of that type is named (a guess could name the wrong pass),
    and every other type still is."""
    text, sources = excerpt
    dropped = sources[-1].types
    after = _op_names(program_scopes.restore(text, sources[:-1]))
    kept = 0
    for n, line in _lines_by_name(text).items():
        if n.startswith("ragged-dot-none"):
            result = re.match(r"\s*%\S+ = (\w+\[[\d,]*\])", line).group(1)
            factors = re.findall(r"\w+\[[\d,]*\]", re.search(r"operand_layout_constraints=\{(.*?)\}, \w+=", line).group(1))
            unnamed = (result, *factors[-2:]) == dropped
            kept += unnamed
            assert (after[n] == "ragged-dot-none") == unnamed, (n, after[n])
    assert 0 < kept < 40


def test_source_products_reads_each_ragged_dot_of_a_tpu_lowering(cfg):
    """The unoptimised module of one ``lower()`` (for a TPU, from the CPU:
    nothing compiles): the expert layer's ten products a block, each with
    its scope, its types and the loop over blocks it sits in."""
    rng = np.random.default_rng(3)
    d, e, f = M["hidden_size"], M["num_experts"], M["moe_intermediate_size"]
    w = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32) * 0.3)
    p = {"router": w(d, e), "w_gate": w(4, d, f), "w_up": w(4, d, f), "w_down": w(4, f, d),
         "shared": {"w_gate": w(d, f), "w_up": w(d, f), "w_down": w(f, d)}, "shared_gate": w(d, 1)}
    x = jnp.zeros((B, d), jnp.float32)
    loss = lambda p, x: stream.expert_layer(p, x, cfg)[0].sum()
    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).trace(p, x).lower(lowering_platforms=("tpu",))
    got = program_scopes.source_products(lowered)
    assert len(got) == 10
    assert all(GROUPED.lstrip("/") in s.op_name for s in got)
    assert all("stream/expert_blocks" in s.loop and s.loop.endswith("/while") for s in got)
    assert sorted("transpose(" in s.op_name for s in got) == [False] * 3 + [True] * 7
    assert {s.types[0] for s in got} == {f"f32[{B},{f}]", f"f32[{B},{d}]", f"f32[4,{d},{f}]", f"f32[4,{f},{d}]"}


# -- the tiny step, compiled on the CPU -----------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_text(cfg):
    rng = np.random.default_rng(0)
    tr = OnlineGraphTrainer(
        OnlineGraphConfig(num_nodes=N, max_neighbors=4, batch_size=B, super_steps=2, model=cfg,
                          queue_capacity=8, train=TrainConfig(seed=2)),
        node_feats=rng.normal(size=(N, 2)).astype(np.float32),
        topo_src=rng.integers(0, N, 200).astype(np.int32), topo_dst=rng.integers(0, N, 200).astype(np.int32),
        topo_rtt=rng.random(200).astype(np.float32),
    )
    try:
        return tr.lower_dispatch().compile().as_text(), tr.dispatch_program_text()
    finally:
        tr.close()


# The trainer's own loop over a dispatch's steps and the step's counters
# (``_train_dispatch``, ``_graph_train_step`` outside ``loss`` and
# ``optimizer``): shared with the hop step, whose text is held to its
# digest (tests/test_stream_ranker.py), so left as jax names them.
_STAGING = re.compile(r"^(jit\(.*\)|while|body|cond|closed_call)$")


def _the_trainers(op_name):
    if not (op_name or "").startswith("jit(_train_dispatch)/"):
        return False
    return len([p for p in op_name.split("/") if not _STAGING.match(p)]) <= 1


def test_every_operation_of_the_tiny_step_names_a_program_scope(tiny_text):
    """The coverage guard: each instruction of ``dispatch_program_text()``
    that runs as an operation and computes names a scope of the program
    (``stream/...``, ``loss``, ``optimizer``; what ``step_unscoped_share``
    counts as named), but for the trainer's own loop and counters.  New
    code outside every scope fails here instead of going unseen."""
    scoped = bench.load_module("metrics", "step_unscoped_share").scoped
    _, text = tiny_text
    ops = [o for o in program_scopes.operations(text) if o[1] not in program_scopes.INERT]
    assert len(ops) > 1000
    unnamed = [o for o in ops if not scoped(o[2]) and not _the_trainers(o[2])]
    assert not unnamed, unnamed[:20]
    trainers = [o for o in ops if _the_trainers(o[2])]
    assert 0 < len(trainers) < 50                  # a loop's count, a sum, a subtraction


def test_the_tiny_steps_text_differs_from_the_compilers_in_op_names_alone(tiny_text):
    compiled, text = tiny_text
    assert text != compiled
    assert _without_op_names(text) == _without_op_names(compiled)


def test_the_hop_steps_text_differs_from_the_compilers_in_op_names_alone():
    """The hop step has no grouped product; the copy rule names its XLA
    copies by their neighbours and changes nothing else."""
    from dragonfly2_tpu.models import HopConfig

    nothing = np.zeros(0, np.int32)
    tr = OnlineGraphTrainer(
        OnlineGraphConfig(num_nodes=64, max_neighbors=4, batch_size=32, super_steps=2,
                          model=HopConfig(hidden=16)),
        node_feats=np.zeros((64, 12), np.float32), topo_src=nothing, topo_dst=nothing,
        topo_rtt=nothing.astype(np.float32),
    )
    try:
        compiled, text = tr.lower_dispatch().compile().as_text(), tr.dispatch_program_text()
    finally:
        tr.close()
    assert _without_op_names(text) == _without_op_names(compiled)
    unnamed = lambda t: sum(1 for o in program_scopes.operations(t) if o[1] == "copy" and o[2] is None)
    assert unnamed(compiled) > unnamed(text) == 0


def test_the_new_scopes_name_the_steps_glue(tiny_text):
    """The loops' own work, the residual adds and the counts each under a
    scope of its own that no existing reader sums (``stream_scopes`` cuts
    none of them to a scope a share adds up)."""
    from benchmark.reduce import stream_scopes
    from benchmark.tools.program_trace import instruction_scopes

    names = set(instruction_scopes(tiny_text[1]).values())
    for scope in ("stream/rows", "stream/expert_blocks", "stream/residual", "stream/segments",
                  "stream/attn/count", "stream/moe/count", "stream/expert_layer"):
        assert any(f"{scope}/" in n for n in names), scope
    summed = {"gdn/scan", "attn/core", *stream_scopes.MOE}
    assert stream_scopes.scope_of("jit(f)/jvp(StreamRanker)/stream/rows/while/body/add") is None
    assert stream_scopes.scope_of("a/stream/residual/add") is None
    assert stream_scopes.scope_of("a/stream/attn/count/add") not in summed
    assert stream_scopes.scope_of("a/stream/moe/count/convert_element_type") not in summed
    assert stream_scopes.scope_of(
        "a/stream/expert_layer/stream/expert_blocks/while/body/stream/moe/experts/grouped/ragged_dot_general"
    ) == "moe/experts"


# -- the two readers on a made-up trace and text ----------------------------------------------------


def _run(text, ops):
    from benchmark.reduce import xplane

    trace = xplane.Trace(devices=[xplane.Device("/device:TPU:0", ops=ops)], spans=[])
    extras = {} if text is None else {"program_text": text}
    return SimpleNamespace(trace=trace, window=SimpleNamespace(extras=extras))


TEXT = "\n".join([
    '  %ragged-dot-none.3 = f32[8]{0} custom-call(%a), metadata={op_name="jit(f)/jvp(StreamRanker)/stream/expert_blocks/while/body/stream/moe/experts/grouped/ragged_dot_general"}',
    '  %ragged-dot-none.4 = f32[8]{0} custom-call(%a), metadata={op_name="jit(f)/transpose(jvp(StreamRanker))/checkpoint/stream/moe/experts/grouped/ragged_dot_general"}',
    '  %fusion.1 = f32[8]{0} fusion(%a), metadata={op_name="jit(f)/jvp(StreamRanker)/stream/moe/experts/mul"}',
    '  %fusion.2 = f32[8]{0} fusion(%a), metadata={op_name="jit(f)/transpose(jvp(loss))/add_any"}',
    '  %fusion.3 = f32[8]{0} fusion(%a), metadata={op_name="jit(f)/optimizer/add"}',
    '  %add.4 = s32[] add(%a, %b), metadata={op_name="jit(_train_dispatch)/while/body/add"}',
    '  %copy.5 = f32[8]{0} copy(%a)',
    '  %while.6 = f32[8]{0} while(%a), metadata={op_name="jit(f)/jvp(StreamRanker)/stream/rows/while"}',
])
OPS = [
    (0.0, 1.0, "%ragged-dot-none.3 = f32[8]{0} custom-call(%a)"),
    (1.0, 3.0, "%ragged-dot-none.4 = f32[8]{0} custom-call(%a)"),
    (3.0, 4.0, "%fusion.1 = f32[8]{0} fusion(%a)"),
    (4.0, 5.0, "%fusion.2 = f32[8]{0} fusion(%a)"),
    (5.0, 6.0, "%fusion.3 = f32[8]{0} fusion(%a)"),
    (6.0, 6.5, "%add.4 = s32[] add(%a, %b)"),
    (6.5, 7.0, "%copy.5 = f32[8]{0} copy(%a)"),
    (7.0, 10.0, "%while.6 = f32[8]{0} while(%a)"), (7.0, 9.0, "%fusion.1 = f32[8]{0} fusion(%a)"),
]


def _read(name, r):
    return bench.load_module("metrics", name).read(r)


def test_moe_products_share_reads_the_grouped_products_own_time():
    assert _read("moe_products_share", _run(TEXT, OPS)) == pytest.approx(100 * 3 / 10)


def test_step_unscoped_share_reads_what_no_scope_names():
    """The trainer's add and the copy with no ``op_name``: 1 s of 10."""
    r = _run(TEXT, OPS)
    assert _read("step_unscoped_share", r) == pytest.approx(100 * 1 / 10)
    assert _read("moe_products_share", r) == pytest.approx(30.0)      # the join, kept on the window


@pytest.mark.parametrize("name", ["moe_products_share", "step_unscoped_share"])
def test_a_run_that_kept_no_program_text_reads_none(name):
    assert _read(name, _run(None, OPS)) is None
    assert _read(name, SimpleNamespace(trace=None, window=SimpleNamespace(extras={"program_text": TEXT}))) is None


def test_a_program_whose_text_names_no_grouped_product_reads_none():
    """A parent from before PR 36: its products carry the compiler's name."""
    text = re.sub(r'(%ragged-dot-none\.\d+ = .*)op_name="[^"]*"', r'\1op_name="ragged-dot-none"', TEXT)
    assert _read("moe_products_share", _run(text, OPS)) is None
    assert _read("step_unscoped_share", _run(text, OPS)) == pytest.approx(100 * 4 / 10)


def test_the_program_scopes_are_read_through_autodiffs_wrappers():
    scoped = bench.load_module("metrics", "step_unscoped_share").scoped
    assert scoped("jit(f)/transpose(jvp(loss))/add_any") and scoped("jit(f)/optimizer/mul")
    assert scoped("jit(f)/jvp(StreamRanker)/stream/residual/add")
    assert not scoped("jit(_train_dispatch)/while/body/add") and not scoped(None)
    assert not scoped("jit(f)/streamer/add") and not scoped("jit(f)/jvp(StreamRanker)/stream")
