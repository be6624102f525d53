"""Device time by library scope (bench/scopes.py).

Two traces recorded on one TPU v5e:

* ``tiny_v5e.xplane.pb`` (see ``test_bench_trace.py``), joined with an HLO
  text written here that gives its instructions scopes, so each rule of the
  join is checked against durations read by hand;
* ``scoped_v5e.xplane.pb`` with ``scoped_v5e.hlo.txt``, the optimized HLO
  of the program that ran (``record_scoped_trace.py``): a matmul under
  ``tiny.matmul``, a while loop whose body runs under ``tiny.body``, and an
  unscoped tanh, three runs.
"""
import pathlib

import pytest

from bench import scopes, trace_reduce

DATA = pathlib.Path(__file__).with_name("data")

# The tiny trace's module, with op_names as the library would give them:
# the matmul fusion has no metadata of its own (its fused ROOT has), the
# while carries none, its body's fusion sits in two nested scopes.
TINY_HLO = """HloModule jit_tiny, entry_computation_layout={(f32[256,256]{1,0})->f32[]}

%fused_computation.1 (param_0: f32[256,256]) -> f32[256,256] {
  %param_0 = f32[256,256]{1,0} parameter(0)
  ROOT %dot.1 = f32[256,256]{1,0} dot(%param_0, %param_0), metadata={op_name="jit(tiny)/tiny.matmul/dot_general"}
}

%fused_computation.clone.clone (param_1: f32[256,256]) -> f32[] {
  %param_1 = f32[256,256]{1,0} parameter(0)
  ROOT %reduce.2 = f32[] reduce(%param_1), metadata={op_name="jit(tiny)/while/body/tiny.loop/tiny.sine/reduce_sum"}
}

ENTRY %main.4 (x.1: f32[256,256]) -> f32[] {
  %x.1 = f32[256,256]{1,0} parameter(0), metadata={op_name="x"}
  %copy-start = (f32[256,256]{1,0}, f32[256,256]{1,0}, u32[]) copy-start(%x.1)
  %copy-done = f32[256,256]{1,0} copy-done(%copy-start)
  %fusion = f32[256,256]{1,0} fusion(%copy-done), kind=kOutput, calls=%fused_computation.1
  %while = (s32[], f32[]) while(%tuple.9), condition=%c, body=%b, metadata={op_name="jit(tiny)/while"}
  ROOT %sine_reduce_fusion.2 = f32[] fusion(%while), kind=kLoop, calls=%fused_computation.clone.clone, metadata={op_name="jit(tiny)/while/body/tiny.loop/tiny.sine/sin"}
}
"""

# tiny_v5e, three runs: 3 while (139,230 ns), holding 60
# sine_reduce_fusion.2 (116,866 ns); 3 fusion (843), 3 copy-start (40),
# 3 copy-done (9).
TINY_BUSY_NS = 139_230 + 843 + 40 + 9


@pytest.fixture(scope="module")
def tiny():
    import jax

    pd = jax.profiler.ProfileData.from_file(str(DATA / "tiny_v5e.xplane.pb"))
    return pd, scopes.reduce_scoped(pd, {"jit_tiny": TINY_HLO})


def test_hlo_scopes_rules():
    table = scopes.hlo_scopes(TINY_HLO)
    assert table["fusion"] == ("tiny.matmul",)          # from its ROOT
    assert table["sine_reduce_fusion.2"] == ("tiny.loop", "tiny.sine")
    assert table["while"] == ()                         # metadata, no scope
    assert table["x.1"] == ()
    assert table["copy-start"] == ()                    # no metadata
    assert scopes.module_name(TINY_HLO) == "jit_tiny"
    with pytest.raises(ValueError):
        scopes.module_name("not hlo")


def test_count_ops_by_scope():
    assert scopes.count_ops(TINY_HLO, "fusion", "tiny.sine") == 1
    assert scopes.count_ops(TINY_HLO, "fusion", "tiny.matmul") == 1
    assert scopes.count_ops(TINY_HLO, "reduce", "tiny.loop") == 1
    assert scopes.count_ops(TINY_HLO, "sort", "tiny.loop") == 0


def test_tiny_scope_seconds(tiny):
    _, red = tiny
    got = red["scopes"]
    # nested scopes count toward each ancestor
    assert got["tiny.loop"] == pytest.approx(116_866e-9, abs=1e-12)
    assert got["tiny.sine"] == pytest.approx(116_866e-9, abs=1e-12)
    assert got["tiny.matmul"] == pytest.approx(843e-9, abs=1e-12)
    assert set(got) == {"tiny.loop", "tiny.sine", "tiny.matmul"}
    assert red["scoped_share"] == pytest.approx(
        (116_866 + 843) / TINY_BUSY_NS, abs=1e-12)
    mod = red["modules"]["jit_tiny"]
    assert mod["device_s"] == pytest.approx(TINY_BUSY_NS / 1e9, abs=1e-12)
    assert mod["scopes"] == got


def test_tiny_top_ops_labels(tiny):
    _, red = tiny
    labels = dict(red["top_ops"])
    assert labels["tiny.sine: sine_reduce_fusion.2 (fusion)"] == \
        pytest.approx(116_866e-9, abs=1e-12)
    assert labels["tiny.matmul: fusion (fusion)"] == \
        pytest.approx(843e-9, abs=1e-12)
    assert labels["while (while)"] == \
        pytest.approx((139_230 - 116_866) / 1e9, abs=1e-12)


def test_base_keys_unchanged(tiny):
    pd, red = tiny
    base = trace_reduce.reduce_profile(pd)
    for key in ("window_s", "busy_s", "devices", "op_kinds", "idle_gaps"):
        assert red[key] == base[key]


def test_unknown_module_is_unscoped(tiny):
    pd, _ = tiny
    red = scopes.reduce_scoped(pd, {})
    assert red["scopes"] == {} and red["scoped_share"] == 0.0
    assert red["top_ops"] == trace_reduce.reduce_profile(pd)["top_ops"]
    assert list(red["modules"]) == ["jit_tiny"]
    assert red["modules"]["jit_tiny"]["scopes"] == {}


def test_nested_op_takes_enclosing_scope(tiny):
    """An op whose metadata names no scope, running inside a scoped while,
    counts toward the while's scopes."""
    pd, _ = tiny
    hlo = (TINY_HLO
           .replace('op_name="jit(tiny)/while"',
                    'op_name="jit(tiny)/tiny.loop/while"')
           .replace("jit(tiny)/while/body/tiny.loop/tiny.sine/sin",
                    "jit(tiny)/while/body/sin"))
    red = scopes.reduce_scoped(pd, {"jit_tiny": hlo})
    assert red["scopes"]["tiny.loop"] == pytest.approx(139_230e-9, abs=1e-12)
    assert "tiny.sine" not in red["scopes"]
    labels = dict(red["top_ops"])
    assert labels["tiny.loop: sine_reduce_fusion.2 (fusion)"] == \
        pytest.approx(116_866e-9, abs=1e-12)


# --- scoped_v5e: the library's own scopes, as the chip's compiler kept them

# XLA Ops over the three runs: 3 copy-start (39 ns), 3 copy-done (9),
# 3 fusion.1 (tiny.matmul, 638 + 637 + 638), 3 while (142,955) holding 60
# fusion.5 (tiny.body, 121,558), 3 copy (830), 3 tanh_bitcast_fusion
# (1,870); nothing overlaps but the fusions inside the whiles.
SCOPED_BODY_NS = 121_558
SCOPED_MATMUL_NS = 1_913
SCOPED_BUSY_NS = 39 + 9 + 1_913 + 142_955 + 830 + 1_870


@pytest.fixture(scope="module")
def scoped():
    import jax

    pd = jax.profiler.ProfileData.from_file(
        str(DATA / "scoped_v5e.xplane.pb"))
    text = (DATA / "scoped_v5e.hlo.txt").read_text()
    return pd, text, scopes.reduce_scoped(pd, {scopes.module_name(text):
                                               text})


def test_scoped_trace_scope_seconds(scoped):
    _, text, red = scoped
    assert scopes.module_name(text) == "jit_scoped"
    assert red["scopes"] == {
        "tiny.body": pytest.approx(SCOPED_BODY_NS / 1e9, abs=1e-12),
        "tiny.matmul": pytest.approx(SCOPED_MATMUL_NS / 1e9, abs=1e-12)}
    assert red["busy_s"] == pytest.approx(SCOPED_BUSY_NS / 1e9, abs=1e-12)
    assert red["scoped_share"] == pytest.approx(
        (SCOPED_BODY_NS + SCOPED_MATMUL_NS) / SCOPED_BUSY_NS, abs=1e-12)
    assert red["modules"]["jit_scoped"]["device_s"] == pytest.approx(
        SCOPED_BUSY_NS / 1e9, abs=1e-12)


def test_scoped_trace_top_ops(scoped):
    _, _, red = scoped
    assert red["top_ops"] == [
        ["tiny.body: fusion.5 (fusion)", pytest.approx(121_558e-9, abs=1e-12)],
        ["while (while)", pytest.approx(21_397e-9, abs=1e-12)],
        ["tiny.matmul: fusion.1 (fusion)", pytest.approx(1_913e-9, abs=1e-12)],
        ["tanh_bitcast_fusion (fusion)", pytest.approx(1_870e-9, abs=1e-12)],
        ["copy (copy)", pytest.approx(830e-9, abs=1e-12)],
        ["copy-start (copy-start)", pytest.approx(39e-9, abs=1e-12)],
        ["copy-done (copy-done)", pytest.approx(9e-9, abs=1e-12)]]


@pytest.mark.parametrize("instruction,want", [
    ("fusion.1", ("tiny.matmul",)),
    ("fusion.5", ("tiny.body",)),      # inside the while body
    ("while", ()),
    ("tanh_bitcast_fusion", ()),
    ("copy-start", ()),
])
def test_scoped_trace_hlo_scopes(scoped, instruction, want):
    _, text, _ = scoped
    assert scopes.hlo_scopes(text)[instruction] == want
