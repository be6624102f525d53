"""The scale-safety analyzer audits itself: every W rule must fire
exactly on its seeded broken fixture and stay silent on the fixed twin;
the lattice transfer functions must be SOUND (brute-force containment
over enumerated concrete inputs); every registered production
configuration must analyze clean at symbolic N = 1e9; and the runtime
behavior the analyzer proves (int64 CSR offsets past 2^31, int64 halo
labels, clamped Morton quantization) is regression-tested at
mocked-large sizes.
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.staticcheck.absint import (SymbolicScale, analyze, audit_routes,
                                      scale_for, CollectiveUse)
from repro.staticcheck.absint_registry import (REGISTERED_ABSINT_AUDITS,
                                               SEEDED_FIXTURES)
from repro.staticcheck.lattice import Ival
from repro.staticcheck import lattice as lat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_SYM = 10**9


def _scale(**kw):
    return SymbolicScale(dims=scale_for(254, N_SYM), **kw)


# --- lattice soundness: brute-force containment ------------------------------

_INTS = [Ival(-6, -2), Ival(-3, 3), Ival(0, 5), Ival(2, 7), Ival(4, 4)]


def _enum(iv):
    return np.arange(int(iv.lo), int(iv.hi) + 1, dtype=np.int64)


@pytest.mark.parametrize("op,ref", [
    ("add", lambda x, y: x + y),
    ("sub", lambda x, y: x - y),
    ("mul", lambda x, y: x * y),
    ("imin", np.minimum),
    ("imax", np.maximum),
])
def test_lattice_binary_ops_contain_all_concrete_results(op, ref):
    f = getattr(lat, op)
    for a in _INTS:
        for b in _INTS:
            out = f(a, b)
            xs, ys = np.meshgrid(_enum(a), _enum(b))
            got = ref(xs, ys)
            assert out.known
            assert out.lo <= got.min() and got.max() <= out.hi, \
                (op, a, b, out, got.min(), got.max())


def test_lattice_division_and_remainder_sound_for_truncating_semantics():
    # jax.lax.div/rem truncate toward zero (C semantics)
    for a in _INTS:
        for b in _INTS:
            xs, ys = np.meshgrid(_enum(a), _enum(b))
            nz = ys != 0
            if not nz.any():
                continue
            q = np.trunc(xs[nz] / ys[nz])
            r = xs[nz] - q * ys[nz]
            # integer div is lat.div composed with truncate (what the
            # interpreter stages for int outputs)
            dq, dr = lat.truncate(lat.div(a, b)), lat.rem(a, b)
            assert dq.lo <= q.min() and q.max() <= dq.hi, (a, b, dq)
            assert dr.lo <= r.min() and r.max() <= dr.hi, (a, b, dr)


def test_lattice_bitwise_and_shifts_sound():
    small = [Ival(0, 7), Ival(2, 11), Ival(5, 5)]
    for a in small:
        for b in small:
            xs, ys = np.meshgrid(_enum(a), _enum(b))
            for op, ref in (("bit_and", np.bitwise_and),
                            ("bit_or", np.bitwise_or),
                            ("bit_xor", np.bitwise_xor)):
                out = getattr(lat, op)(a, b)
                got = ref(xs, ys)
                assert out.lo <= got.min() and got.max() <= out.hi, (op, a, b)
        for sh in (Ival(0, 3), Ival(1, 1)):
            xs, ys = np.meshgrid(_enum(a), _enum(sh))
            out = lat.shift_left(a, sh)
            got = xs << ys
            assert out.lo <= got.min() and got.max() <= out.hi, (a, sh, out)
            out = lat.shift_right(a, sh, arithmetic=True)
            got = xs >> ys
            assert out.lo <= got.min() and got.max() <= out.hi, (a, sh, out)


def test_lattice_unary_and_float_quantizers_sound():
    for a in _INTS:
        xs = _enum(a)
        for op, ref in (("neg", np.negative), ("iabs", np.abs)):
            out = getattr(lat, op)(a)
            got = ref(xs)
            assert out.lo <= got.min() and got.max() <= out.hi, (op, a)
    floats = [Ival(-2.75, 3.25), Ival(0.1, 0.9), Ival(-5.5, -1.5)]
    for a in floats:
        xs = np.linspace(a.lo, a.hi, 37)
        for op, ref in (("floor_op", np.floor), ("ceil_op", np.ceil),
                        ("round_op", np.round), ("truncate", np.trunc)):
            out = getattr(lat, op)(a)
            got = ref(xs)
            assert out.lo <= got.min() and got.max() <= out.hi, (op, a)


def test_lattice_join_meet_wrap():
    a, b = Ival(0, 5), Ival(3, 9)
    assert lat.join(a, b) == Ival(0, 9, True)
    assert lat.meet(a, b) == Ival(3, 5, True)
    assert lat.meet(Ival(0, 2), Ival(5, 9)) is None
    # uint32 wrap: an interval spanning the modulus degrades to full range
    w = lat.wrap_unsigned(Ival(-1, 1), jnp.dtype(jnp.uint32))
    assert w.lo == 0 and w.hi == 2**32 - 1


# --- seeded fixtures: each W rule fires, and only where seeded ---------------

@pytest.mark.parametrize("audit", SEEDED_FIXTURES, ids=lambda a: a.name)
def test_seeded_fixture_fires_its_rule(audit):
    rep = audit.run(True)
    fired = sorted({f.rule for f in rep.findings})
    assert fired == sorted(set(audit.expect_rules)), \
        [str(f) for f in rep.findings]
    if "W3-routes" not in audit.expect_rules:
        # value-level rules localize to ONE eqn; route tables may trip
        # several invariants at once
        assert len(rep.findings) == 1, [str(f) for f in rep.findings]


def test_fixed_twin_min_image_is_silent():
    L = 100.0

    def min_image_fixed(dx):
        dxc = jnp.clip(dx, -L, L)
        return dxc - jnp.round(dxc / L) * L

    rep = analyze(min_image_fixed, (jnp.zeros((254,), jnp.float32),),
                  name="minimg_fixed", scale=_scale(),
                  input_ivals=[Ival(-1.0e15, 1.0e15)])
    assert rep.findings == []


def test_fixed_twin_clipped_gather_is_silent():
    lab = jnp.zeros((254,), jnp.int32)
    idx = jnp.zeros((254,), jnp.int32)
    rep = analyze(lambda l, i: l[jnp.clip(i, 0, 253)], (lab, idx),
                  name="gather_fixed", scale=_scale(),
                  input_ivals=[Ival(0, 100), Ival(0, N_SYM)])
    assert rep.findings == []


def test_fixed_twin_f64_subtraction_meets_precision_floor():
    with jax.enable_x64(True):
        a = jnp.zeros((254,), jnp.float64)
        rep = analyze(lambda x, y: x - y, (a, a), name="cancel_f64",
                      scale=_scale(precision_floor=1e-3),
                      input_ivals=[Ival(1.0e9, 1.1e9), Ival(1.0e9, 1.1e9)])
    assert rep.findings == []


# --- analyzer mechanics ------------------------------------------------------

def test_scan_linear_widening_catches_accumulator_overflow():
    def acc(x):
        def body(c, xi):
            return c + xi, xi
        out, _ = jax.lax.scan(body, jnp.int32(0), x)
        return out

    rep = analyze(acc, (jnp.ones((254,), jnp.int32),), name="scan_acc",
                  scale=_scale(), input_ivals=[Ival(0, 2048)])
    assert [f.rule for f in rep.findings] == ["W1-index-width"]


def test_negative_index_canonicalization_not_flagged():
    # x[i] for i in [-N, N-1] stages lt/add/select_n; guard refinement must
    # keep both branches in [0, N-1]
    x = jnp.zeros((254,), jnp.float32)
    i = jnp.zeros((254,), jnp.int32)
    rep = analyze(lambda a, j: a[j], (x, i), name="neg_idx", scale=_scale(),
                  input_ivals=[None, Ival(-N_SYM, N_SYM - 1)])
    assert rep.findings == []


def test_literal_program_output_does_not_stop_the_analysis():
    # a jaxpr whose outputs include a constant (a Literal, unhashable) next
    # to a signed integer op that overflows at symbolic N
    rep = analyze(lambda a: (jnp.sum(a) * 3, jnp.float32(1.0)),
                  (jnp.ones((254,), jnp.int32),), name="literal_out",
                  scale=_scale(), input_ivals=[Ival(0, 2048)])
    assert [f.rule for f in rep.findings] == ["W1-index-width"]


def test_cross_pjit_where_refinement():
    # jnp.where stages a pjit: the select_n sits one jaxpr below the
    # comparison producing its predicate. The sentinel-guarded index must
    # still refine to in-bounds.
    lab = jnp.zeros((254,), jnp.int32)
    i = jnp.zeros((254,), jnp.int32)

    def f(l, j):
        jj = jnp.where(j < l.shape[0], j, 0)
        return l[jj]

    rep = analyze(f, (lab, i), name="where_refine", scale=_scale(),
                  input_ivals=[Ival(0, 100), Ival(0, N_SYM)])
    assert rep.findings == []


def test_unsigned_wraparound_is_legal():
    # Morton-style magic-number multiply overflows uint32 by design
    def magic(v):
        v = v.astype(jnp.uint32) & jnp.uint32(0x3FF)
        return (v * jnp.uint32(0x00010001)) & jnp.uint32(0xFF0000FF)

    rep = analyze(magic, (jnp.zeros((254,), jnp.int32),), name="magic",
                  scale=_scale(), input_ivals=[Ival(0, 1023)])
    assert rep.findings == []


def test_symbolic_scale_reads_markers():
    sc = SymbolicScale(dims=scale_for(254, N_SYM))
    assert sc.dim(254) == N_SYM and sc.dim(253) == N_SYM - 1
    assert sc.dim(507) == 2 * N_SYM - 1 and sc.dim(17) == 17
    assert sc.lit(254) == N_SYM and sc.lit(True) is True
    assert sc.axis_size("data", 1) == 1
    assert SymbolicScale(axes={"data": 64}).axis_size("data", 1) == 64


def test_audit_routes_unit():
    mesh = {"data": 4}
    good = CollectiveUse("ppermute", ("data",),
                         ((0, 1), (1, 2), (2, 3), (3, 0)), mesh)
    assert audit_routes([good], "t") == []
    dup_dst = CollectiveUse("ppermute", ("data",), ((0, 1), (2, 1)), mesh)
    oob = CollectiveUse("ppermute", ("data",), ((0, 7),), mesh)
    bad_axis = CollectiveUse("psum", ("model",), (), mesh)
    msgs = [f.message for f in audit_routes([dup_dst, oob, bad_axis], "t")]
    assert any("duplicate destination" in m for m in msgs)
    assert any("outside the mesh axis" in m for m in msgs)
    assert any("not an axis of the enclosing mesh" in m for m in msgs)


# --- registered production configurations analyze clean ----------------------

@pytest.mark.parametrize("audit", REGISTERED_ABSINT_AUDITS,
                         ids=lambda a: a.name)
def test_registered_absint_audit_clean(audit):
    rep = audit.run(False)
    assert rep.findings == [], [str(f) for f in rep.findings]
    assert rep.values_analyzed > 0
    assert rep.unknown_prims == 0, \
        f"{rep.name}: {rep.unknown_prims} unmodelled primitives"


# --- the proved behavior, executed: index-width regression tests -------------

def test_csr_offsets_int64_past_2_31_at_mocked_large_counts():
    from repro.core.bvh import build_bvh
    from repro.core.geometry import scene_bounds
    from repro.core.query import query_csr_device, within

    with jax.enable_x64(True):
        pts = jnp.asarray(np.random.default_rng(0).random((4, 3)),
                          jnp.float32)
        lo, hi = scene_bounds(pts)
        bvh = build_bvh(pts, lo, hi)
        counts = jnp.full((4,), 2**30, jnp.int64)  # 4 * 2^30 = 2^32 hits
        csr = query_csr_device(bvh, within(pts, 0.1), 8, counts=counts,
                               index_dtype=jnp.int64)
        assert csr.offsets.dtype == jnp.dtype(jnp.int64)
        assert int(csr.offsets[-1]) == 2**32      # int32 would wrap to 0
        assert int(csr.total) == 2**32
        assert bool(csr.overflowed)


def test_csr_int64_requires_x64():
    from repro.core.query import _canon_index_dtype

    if jax.config.jax_enable_x64:
        pytest.skip("x64 globally enabled")
    with pytest.raises(ValueError, match="x64"):
        _canon_index_dtype(jnp.int64)
    assert _canon_index_dtype(jnp.int32) == jnp.dtype(jnp.int32)
    with pytest.raises(ValueError, match="int32 or int64"):
        _canon_index_dtype(jnp.float32)


def test_halo_catalog_labels_follow_int64_dtype():
    from repro.halos.catalog import canonicalize_labels, _sort_last

    with jax.enable_x64(True):
        # global ids beyond 2^31: the int32 sort sentinel (2^31-1) would
        # sort REAL labels after noise
        big = 2**31 + 5
        labels = jnp.asarray([big, -1, big, 7], jnp.int64)
        perm, pid_s, lab_s, member_s, nprov, _ = \
            canonicalize_labels(labels, capacity=4)
        assert lab_s.dtype == jnp.dtype(jnp.int64)
        assert int(_sort_last(jnp.int64)) == 2**63 - 1
        # noise sorts last, both big-label particles share a dense id
        assert not bool(member_s[-1])
        assert int(lab_s[0]) == 7 and int(lab_s[1]) == big
        assert int(pid_s[1]) == int(pid_s[2]) == 1
        assert int(nprov) == 2


def test_morton_quantize_clamps_before_cast():
    from repro.core.morton import _quantize, morton64

    big = jnp.asarray([[1.0e15, -1.0e15, 0.5]], jnp.float32)
    q = _quantize(big, 1 << 21)
    assert q.dtype == jnp.dtype(jnp.uint32)
    assert int(q[0, 0]) == (1 << 21) - 1 and int(q[0, 1]) == 0
    hi, lo = morton64(big)  # must not overflow the cast
    assert hi.dtype == lo.dtype == jnp.dtype(jnp.uint32)


# --- CLI contract ------------------------------------------------------------

def test_cli_absint_clean_tree_exits_zero(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    report = tmp_path / "sc.json"
    absint_report = tmp_path / "absint.json"
    out = subprocess.run(
        [sys.executable, "-m", "repro.staticcheck",
         os.path.join(REPO, "src", "repro"), "--absint", "--fast",
         "--json", str(report), "--absint-json", str(absint_report)],
        capture_output=True, text=True, env=env, cwd=str(tmp_path))
    assert out.returncode == 0, out.stdout + out.stderr
    data = json.loads(absint_report.read_text())
    assert data["ok"]
    names = [e["name"] for e in data["entrypoints"]]
    assert "query_csr_device[int64]" in names and "fdbscan" in names
    assert all(e["findings"] == [] for e in data["entrypoints"])
    assert sum(e["values_analyzed"] for e in data["entrypoints"]) > 1000
