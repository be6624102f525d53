"""Trace reduction, checked on a small trace recorded on one TPU v5e.

The trace holds three runs of one jitted program (a 256 x 256 matmul, then
a 20-step while loop of a sine-and-sum fusion), each inside a
``bench:tiny<k>`` host annotation, with 20 ms host sleeps between them.
The numbers below were read by hand from the file's events."""
import pathlib

import pytest

from bench import trace_reduce

DATA = pathlib.Path(__file__).with_name("data") / "tiny_v5e.xplane.pb"

# Window: bench:tiny0 starts at 47,756,729 ns; bench:tiny2 starts at
# 91,326,178 ns and lasts 615,810 ns.
WINDOW_NS = 91_326_178 + 615_810 - 47_756_729
# XLA Ops, over the three runs: 3 while (139,230 ns together), holding 60
# sine_reduce_fusion.2 (116,866 ns); 3 fusion (843), 3 copy-start (40),
# 3 copy-done (9); none overlaps another except the fusions inside the
# whiles.
BUSY_NS = 139_230 + 843 + 40 + 9


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce_file(DATA)


def test_window_and_idle_share(red):
    assert red["window_s"] == pytest.approx(WINDOW_NS / 1e9, abs=1e-12)
    assert red["busy_s"] == pytest.approx(BUSY_NS / 1e9, abs=1e-12)
    assert [d["id"] for d in red["devices"]] == [0]
    idle = 1 - red["busy_s"] / red["window_s"]
    assert idle == pytest.approx(1 - 140_122 / 44_185_259, abs=1e-12)


def test_self_time_by_op_kind(red):
    kinds = red["op_kinds"]
    assert kinds["while"] == pytest.approx((139_230 - 116_866) / 1e9,
                                           abs=1e-12)
    assert kinds["fusion"] == pytest.approx((116_866 + 843) / 1e9, abs=1e-12)
    assert kinds["copy-start"] == pytest.approx(40e-9, abs=1e-12)
    assert kinds["copy-done"] == pytest.approx(9e-9, abs=1e-12)
    assert sum(kinds.values()) == pytest.approx(BUSY_NS / 1e9, abs=1e-12)
    assert red["top_ops"][0][0] == "sine_reduce_fusion.2 (fusion)"


def test_idle_gaps_are_named_by_the_host(red):
    gaps = red["idle_gaps"]
    assert len(gaps) == 10
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    # the two 20 ms host sleeps between the annotated runs
    assert gaps[0][0] == gaps[1][0] == "between stages: $time sleep"
    assert 0.020 < gaps[1][1] <= gaps[0][1] < 0.023


@pytest.mark.parametrize("name,want", [
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", ("fusion.3",
                                                               "fusion")),
    ("%while.2 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)",
     ("while.2", "while")),
    ("%all-gather-start = (f32[4]{0}, f32[16]{0}) all-gather-start(f32[4]"
     "{0} %x)", ("all-gather-start", "all-gather-start")),
    ("copy", ("copy", "copy")),
])
def test_op_kind(name, want):
    assert trace_reduce.op_kind(name) == want
