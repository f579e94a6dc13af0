"""``tools/program_trace.py`` on the recorded chip trace and on made-up
spans: gaps named by the program's spans, operations summed by the scope
their instruction carries in a program's text."""

import gzip
import os
import shutil

import pytest

from benchmark.reduce import xplane
from benchmark.tools import program_trace as pt

HERE = os.path.dirname(os.path.abspath(__file__))

TEXT = """
HloModule jit__train_dispatch, entry_computation_layout={...}

%fused_computation.1 (p: bf16[8,4]) -> bf16[8,4] {
  %dot.3 = bf16[8,4]{1,0} dot(...), metadata={op_type="dot_general" op_name="jit(_train_dispatch)/while/body/closed_call/jvp(HopRanker)/hop/src/HopEncoder_0/Dense_1/dot_general" source_file="hop.py" source_line=120}
}

  %convolution_add_fusion.13 = bf16[524288,1024]{1,0:T(8,128)(2,1)} fusion(%a, %b), kind=kOutput, calls=%fused_computation.1, metadata={op_type="dot_general" op_name="jit(_train_dispatch)/while/body/closed_call/jvp(HopRanker)/hop/src/HopEncoder_0/Dense_1/dot_general" source_file="hop.py"}
  fusion.560 = bf16[1024,1024]{1,0} fusion(%c), kind=kOutput, metadata={op_name="jit(_train_dispatch)/while/body/closed_call/transpose(jvp(HopRanker))/hop/dst/HopEncoder_0/Dense_1/dot_general"}
  ROOT %fusion.563 = f32[100000,32]{1,0} fusion(%d), kind=kLoop, metadata={op_name="jit(_train_dispatch)/while/body/closed_call/optimizer/mul"}
  %copy.7 = f32[8]{0} copy(%e)
"""


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(os.path.join(HERE, "data", "online-steady-3s.xplane.pb.gz")) as src:
        with open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
    return str(path)


@pytest.mark.parametrize("op_name,want", [
    ("jit(_train_dispatch)/while/body/closed_call/jvp(HopRanker)/hop/src/HopEncoder_0/Dense_1/dot_general",
     ("hop/src fwd", "HopEncoder_0/Dense_1/dot_general")),
    ("jit(_train_dispatch)/while/body/closed_call/transpose(jvp(HopRanker))/hop/gather/jit(_take)/scatter-add",
     ("hop/gather bwd", "scatter-add")),
    ("jit(<lambda>)/transpose(jvp(GATRanker))/GATLayer_1/gat/gather/jit(_take)/scatter-add",
     ("gat/gather bwd", "scatter-add")),
    ("jit(_train_dispatch)/while/body/closed_call/transpose(jvp(loss))/add_any", ("loss bwd", "add_any")),
    ("jit(_train_dispatch)/while/body/closed_call/jvp(loss)/abs", ("loss fwd", "abs")),
    ("jit(_train_dispatch)/while/body/closed_call/optimizer/div", ("optimizer", "div")),
    ("jit(_train_dispatch)/while/body/closed_call/jit(_threefry_fold_in)/_graph_train_step/while/body/closed_call/or",
     ("(outside every scope)", "or")),
])
def test_an_op_name_is_cut_down_to_its_scope(op_name, want):
    assert pt.scope_of(op_name) == want


def test_instructions_are_found_with_and_without_a_percent_sign():
    got = pt.instruction_scopes(TEXT)
    assert set(got) == {"dot.3", "convolution_add_fusion.13", "fusion.560", "fusion.563"}
    assert got["fusion.563"].endswith("optimizer/mul")


def test_device_time_is_summed_by_scope_on_the_recorded_run(recorded):
    trace = pt.load(recorded)
    by_scope, by_op = pt.scope_times(trace, pt.instruction_scopes(TEXT))
    by_scope = dict(by_scope)
    assert set(by_scope) == {"hop/src fwd", "hop/dst bwd", "optimizer", "(no op_name)"}
    # What run.py's own breakdown gave these operations (test_xplane_recorded).
    assert by_scope["hop/src fwd"] == pytest.approx(0.7012696389999986, rel=1e-6)
    assert sum(by_scope.values()) == pytest.approx(trace.busy_s(), rel=1e-6)
    named = [row for row in by_op if row[1] != "(no op_name)"]
    assert named[0][0].startswith("%convolution_add_fusion.13 bf16[524288,1024]")
    assert named[0][1:3] == ["hop/src fwd", "HopEncoder_0/Dense_1/dot_general"]


def test_a_gap_takes_the_innermost_program_span_over_the_benchmarks():
    dev = xplane.Device("/device:TPU:0", ops=[(0.0, 1.0, "a"), (1.5, 2.0, "b"), (2.0005, 3.0, "c"), (3.2, 4.0, "d")])
    spans = [
        (0.0, 4.0, "bench/window"), (0.9, 3.5, "trainer/run"), (1.1, 1.4, "trainer/next_block"),
        (1.2, 1.3, "bench/feed_blocked"),
    ]
    gaps = pt.named_gaps(xplane.Trace(devices=[dev], spans=spans))
    assert [(round(a, 3), round(n, 3), name) for a, n, name in gaps] == [
        (1.0, 0.5, "trainer/next_block"), (3.0, 0.2, "trainer/run"),      # the 0.5 ms gap is left out
    ]
    assert pt.named_gaps(xplane.Trace(devices=[dev], spans=spans[:1]))[0][2] == "bench/window"
    assert pt.named_gaps(xplane.Trace(devices=[dev], spans=[]))[0][2] == "unattributed"


def test_own_time_of_the_programs_spans():
    spans = [
        (0.0, 10.0, "bench/run"), (0.0, 10.0, "trainer/run"),
        (0.0, 4.0, "trainer/next_block"), (4.5, 5.5, "trainer/dispatch"),
        (4.6, 4.8, "trainer/h2d"), (4.8, 5.4, "trainer/enqueue"),
        (6.0, 9.0, "trainer/next_block"),
    ]
    got = {name: (n, total, own) for name, n, total, own in pt.span_times(xplane.Trace([], spans))}
    assert set(got) == {"trainer/run", "trainer/next_block", "trainer/dispatch", "trainer/h2d", "trainer/enqueue"}
    assert got["trainer/run"] == (1, 10.0, pytest.approx(2.0))
    assert got["trainer/next_block"] == (2, 7.0, pytest.approx(7.0))
    assert got["trainer/dispatch"] == (1, 1.0, pytest.approx(0.2))


def test_main_prints_both_tables(recorded, monkeypatch, capsys):
    monkeypatch.setattr(pt, "online_program_text", lambda workload: TEXT)
    assert pt.main([recorded, "--workload", "hop-h1024.online-steady", "--top", "3"]) == 0
    out = capsys.readouterr().out
    assert "idle gaps of at least 1 ms on /device:TPU:0: 0" in out
    assert "own device time by scope" in out and "hop/src fwd" in out
    assert pt.main([recorded]) == 0
    assert "per-scope table left out" in capsys.readouterr().out
    # The load's wider prefix is put back: run.py's reduction is untouched.
    assert xplane.ANNOTATION_PREFIX == "bench/"
