"""The invariant auditor audits itself: every rule must fire on a seeded
violation (exactly one finding), stay silent on the compliant variant,
and the CLEAN TREE must produce zero findings — plus one registered
jaxpr audit per production entry point (parametrized), and the CLI's
exit-code / JSON-report contract.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.staticcheck import (REGISTERED_AUDITS, audit_jaxpr,
                               bounded_recompiles, count_compile_signatures,
                               lint_paths, lint_source,
                               max_intermediate_elems, no_dense_intermediate,
                               no_host_transfer)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC_REPRO = os.path.join(REPO, "src", "repro")


# --- AST rules: one seeded violation each, compliant twins stay silent -------

_R1_BAD = """
import jax, jax.numpy as jnp
def bad_walk(bvh, q):
    def cond(s):
        return s[0] != -1
    def body(s):
        node, acc = s
        return bvh.rope[node], acc + bvh.node_lo[node].sum()
    return jax.lax.while_loop(cond, body, (jnp.int32(0), 0.0))
"""

_R1_OK_UNION_FIND = """
import jax, jax.numpy as jnp
def union_fixpoint(parent0):
    def cond(s):
        return s[1]
    def body(s):
        p, _ = s
        p2 = jnp.minimum(p, p[p])
        return p2, jnp.any(p2 != p)
    return jax.lax.while_loop(cond, body, (parent0, jnp.bool_(True)))
"""

_R2_BAD_DECORATOR = """
import jax, functools
from jax import shard_map
@functools.partial(jax.jit, static_argnames=("n",))
def driver(x, mesh, n):
    return shard_map(lambda a: a, mesh=mesh, in_specs=None, out_specs=None)(x)
"""

_R2_BAD_CALL = """
import jax
from jax import shard_map
def driver(x, mesh):
    return shard_map(lambda a: a, mesh=mesh, in_specs=None, out_specs=None)(x)
run = jax.jit(driver)
"""

_R2_OK_GATED = """
from jax import shard_map
from repro.core.distributed import _maybe_jit
@_maybe_jit
def driver(x, mesh):
    return shard_map(lambda a: a, mesh=mesh, in_specs=None, out_specs=None)(x)
"""

_R3_BAD = """
from repro.core.query import query_csr_device
def consume(bvh, pred):
    res = query_csr_device(bvh, pred, 128)
    return res.indices
"""

_R3_OK_CHECKED = """
from repro.core.query import query_csr_device
def consume(bvh, pred):
    res = query_csr_device(bvh, pred, 128)
    assert not bool(res.overflowed)
    return res.indices
"""

_R3_OK_RETURNED = """
from repro.core.query import query_csr
def passthrough(bvh, pred):
    return query_csr(bvh, pred)
"""

_R3_OK_PRAGMA = """
from repro.core.query import query_csr_device
def consume(bvh, pred):
    res = query_csr_device(bvh, pred, 128)  # staticcheck: overflow-ok
    return res.indices
"""

_R4_BAD = """
import jax.numpy as jnp
def fold(diff, L):
    return diff - jnp.round(diff / L) * L
"""

_R4_OK_GUARDED = """
import jax.numpy as jnp
def fold(diff, L):
    k = jnp.where(jnp.abs(diff) > 2 * L, 0.0, jnp.round(diff / L))
    return diff - k * L
"""

_R4_OK_NOT_MINIMAGE = """
import jax.numpy as jnp
def quantize(g, scale):
    return jnp.clip(jnp.round(g / scale), -127, 127)
"""


@pytest.mark.parametrize("rule,src", [
    ("R1-bvh-loop-outside-engine", _R1_BAD),
    ("R2-unguarded-shard-map-jit", _R2_BAD_DECORATOR),
    ("R2-unguarded-shard-map-jit", _R2_BAD_CALL),
    ("R3-unchecked-csr-overflow", _R3_BAD),
    ("R4-unguarded-minimage-fold", _R4_BAD),
])
def test_seeded_violation_fires_exactly_once(rule, src):
    findings = lint_source(textwrap.dedent(src), "fixture.py")
    assert len(findings) == 1, findings
    assert findings[0].rule == rule
    assert findings[0].line > 0


@pytest.mark.parametrize("src", [
    _R1_OK_UNION_FIND, _R2_OK_GATED, _R3_OK_CHECKED, _R3_OK_RETURNED,
    _R3_OK_PRAGMA, _R4_OK_GUARDED, _R4_OK_NOT_MINIMAGE,
])
def test_compliant_variant_is_silent(src):
    assert lint_source(textwrap.dedent(src), "fixture.py") == []


def test_engine_file_exempt_from_r1():
    findings = lint_source(textwrap.dedent(_R1_BAD), "src/repro/core/query.py")
    assert findings == []


def test_wavefront_kernel_module_exempt_from_r1():
    """kernels/wavefront.py is the blessed second home of BVH loops (the
    engine's backend='pallas' kernel body)."""
    findings = lint_source(textwrap.dedent(_R1_BAD),
                           "src/repro/kernels/wavefront.py")
    assert findings == []


def test_r1_still_fires_in_unblessed_kernels_module():
    """The allowlist is the wavefront module, not the kernels package: a
    rogue rope loop in any OTHER kernels/ file keeps the one-fire
    contract."""
    findings = lint_source(textwrap.dedent(_R1_BAD),
                           "src/repro/kernels/rogue.py")
    assert [f.rule for f in findings] == ["R1-bvh-loop-outside-engine"]


def test_generic_ignore_pragma():
    src = _R4_BAD.replace("jnp.round(diff / L) * L",
                          "jnp.round(diff / L) * L  # staticcheck: ignore")
    assert lint_source(textwrap.dedent(src), "fixture.py") == []


def test_clean_tree_has_zero_findings():
    findings, checked = lint_paths([SRC_REPRO])
    assert checked > 50            # the walk really saw the package
    assert findings == [], [str(f) for f in findings]


# --- jaxpr rules -------------------------------------------------------------

def test_no_dense_intermediate_fires_on_dense_staging():
    x = jnp.ones((64, 3))

    def dense(a):
        return ((a[:, None, :] - a[None, :, :]) ** 2).sum(-1)

    findings = audit_jaxpr(dense, (x,), [no_dense_intermediate(64 * 64)])
    assert len(findings) == 1
    assert findings[0].rule == "no-dense-intermediate"
    # and the walker is really measuring: the dense broadcast is visible
    assert max_intermediate_elems(dense, (x,)) >= 64 * 64


def test_no_dense_intermediate_silent_on_linear_fn():
    x = jnp.ones((64, 3))
    findings = audit_jaxpr(lambda a: (a * 2).sum(0), (x,),
                           [no_dense_intermediate(64 * 64), no_host_transfer()])
    assert findings == []


def test_no_host_transfer_fires_on_callback_and_device_put():
    x = jnp.ones((8,))

    def cb(a):
        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct(a.shape, a.dtype), a)

    f1 = audit_jaxpr(cb, (x,), [no_host_transfer()])
    assert len(f1) == 1 and "pure_callback" in f1[0].message

    f2 = audit_jaxpr(lambda a: jax.device_put(a) + 1, (x,),
                     [no_host_transfer()])
    assert len(f2) == 1 and "device_put" in f2[0].message


def test_bounded_recompiles():
    fn = lambda q: (q ** 2).sum()
    unbucketed = [(jnp.ones((n, 3)),) for n in range(1, 9)]
    bucketed = [(jnp.ones((8, 3)),)] * 8
    assert count_compile_signatures(unbucketed) == 8
    assert count_compile_signatures(bucketed) == 1
    assert len(bounded_recompiles(fn, unbucketed, 3)) == 1
    assert bounded_recompiles(fn, bucketed, 3) == []


# --- registered production audits (one test per entry point) -----------------

@pytest.mark.parametrize("audit", REGISTERED_AUDITS, ids=lambda a: a.name)
def test_registered_audit_is_clean(audit):
    assert audit.run(True) == []


# --- CLI contract ------------------------------------------------------------

def _run_cli(args, cwd):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run([sys.executable, "-m", "repro.staticcheck", *args],
                          capture_output=True, text=True, env=env, cwd=cwd)


def test_cli_clean_tree_exits_zero(tmp_path):
    report = tmp_path / "report.json"
    out = _run_cli([SRC_REPRO, "--json", str(report)], cwd=str(tmp_path))
    assert out.returncode == 0, out.stdout + out.stderr
    data = json.loads(report.read_text())
    assert data["ok"] and data["findings"] == []
    assert data["checked_files"] > 50


def test_cli_seeded_violation_exits_nonzero_with_location(tmp_path):
    bad = tmp_path / "violation.py"
    bad.write_text(textwrap.dedent(_R4_BAD))
    report = tmp_path / "report.json"
    out = _run_cli([str(bad), "--json", str(report)], cwd=str(tmp_path))
    assert out.returncode == 1
    data = json.loads(report.read_text())
    assert not data["ok"] and len(data["findings"]) == 1
    f = data["findings"][0]
    assert f["path"] == str(bad) and f["line"] == 4
    assert f"{bad}:4" in out.stdout   # file:line in the human output too
