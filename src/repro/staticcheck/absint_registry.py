"""Registered scale-safety (absint) audits: the repo's device pipelines,
each staged at toy marker sizes and re-read at **symbolic exascale N**
(1e9 points, 64 shards, avg degree 64) by the abstract interpreter.

Two families live here:

* ``REGISTERED_ABSINT_AUDITS`` — the production configurations (int64
  index dtypes under x64 where capacity crosses 2^31). These must
  analyze CLEAN at symbolic N; any finding is a CI failure
  (``python -m repro.staticcheck --absint``). Each entry also feeds one
  parametrized test in ``tests/test_absint.py``.
* ``SEEDED_FIXTURES`` — the broken twins (int32 indices at 64e9 total
  hits, the f32 min-image fold of BIG ghost fills, an out-of-mesh
  collective route). Each must fire EXACTLY its seeded rule — they pin
  the analyzer's recall the same way the clean audits pin its precision.

Sizes are markers, not workloads: ``N_STAGE = 254`` points stage the
jaxpr, ``scale_for(N_STAGE, N_SYM)`` re-reads every shape and literal
equal to a marker at the symbolic size. Tracing stays sub-second; no
giant array is ever materialized.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

from repro.staticcheck.absint import (AbsintReport, SymbolicScale, analyze,
                                      scale_for)
from repro.staticcheck.findings import Finding
from repro.staticcheck.lattice import Ival

__all__ = [
    "AbsintAudit",
    "REGISTERED_ABSINT_AUDITS",
    "SEEDED_FIXTURES",
    "run_absint_audits",
    "absint_coverage",
    "N_STAGE",
    "N_SYM",
    "AVG_DEGREE",
    "N_SHARDS",
]

N_STAGE = 254          # staged marker size (distinct from small constants)
N_SYM = 10**9          # the paper's exascale point count
AVG_DEGREE = 64        # mean neighbors/query -> 64e9 total CSR hits
N_SHARDS = 64          # symbolic mesh width
_CSR_CAP = 318         # staged capacity marker for the CSR paths
_SHARD_CAP = 322       # staged capacity marker for the sharded path
_HALO_CAP = 33


@dataclasses.dataclass(frozen=True)
class AbsintAudit:
    """One symbolic-scale analysis of a registered entry point.

    ``run(fast)`` returns the ``AbsintReport``; ``expect_rules`` is the
    exact set of rule names that must fire (empty for the clean
    production configs). ``allow`` drops findings of the named rules
    before judging — the programmatic counterpart of the source-level
    ``# staticcheck: width-ok`` pragma for values that cannot carry one
    (they live in a traced jaxpr, not a source line).
    """
    name: str
    run: Callable[[bool], AbsintReport]
    expect_rules: tuple = ()
    allow: tuple = ()


def _points(n: int = N_STAGE):
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.random((n, 3), dtype=np.float32))


def _csr_args():
    import jax.numpy as jnp
    from repro.core.bvh import build_bvh
    from repro.core.geometry import scene_bounds
    from repro.core.query import within

    pts = _points()
    lo, hi = scene_bounds(pts)
    bvh = build_bvh(pts, lo, hi)
    pred = within(pts, 0.1)
    counts = jnp.zeros((N_STAGE,), jnp.int32)
    return bvh, pred, counts


def _csr_scale() -> SymbolicScale:
    return SymbolicScale(dims=scale_for(
        N_STAGE, N_SYM,
        {_CSR_CAP: AVG_DEGREE * N_SYM, _CSR_CAP + 1: AVG_DEGREE * N_SYM + 1}))


def _run_csr(fast: bool, index_dtype, x64: bool) -> AbsintReport:
    import jax.numpy as jnp
    from repro.core.query import query_csr_device

    bvh, pred, counts = _csr_args()
    return analyze(
        lambda b, p, c: query_csr_device(b, p, _CSR_CAP, counts=c,
                                         index_dtype=index_dtype),
        (bvh, pred, counts),
        name=f"query_csr_device[{jnp.dtype(index_dtype).name}]",
        scale=_csr_scale(),
        # per-query hit counts: anything up to the capacity marker — it is
        # the 1e9-query cumsum that must not overflow the offsets dtype
        input_ivals=[None, None, Ival(0, 2048)], x64=x64)


def _audit_csr_int64(fast: bool) -> AbsintReport:
    import jax.numpy as jnp
    return _run_csr(fast, jnp.int64, x64=True)


def _fixture_csr_int32(fast: bool) -> AbsintReport:
    import jax.numpy as jnp
    return _run_csr(fast, jnp.int32, x64=False)


def _run_dbscan(fast: bool, pair: bool) -> AbsintReport:
    from repro.core.dbscan import fdbscan, fdbscan_pair

    fn = fdbscan_pair if pair else fdbscan
    pts = _points()
    return analyze(lambda p: fn(p, 0.05, 2), (pts,),
                   name="fdbscan_pair" if pair else "fdbscan",
                   scale=SymbolicScale(dims=scale_for(N_STAGE, N_SYM)),
                   input_ivals=[Ival(0.0, 1.0)])


def _audit_fdbscan(fast: bool) -> AbsintReport:
    return _run_dbscan(fast, pair=False)


def _audit_fdbscan_pair(fast: bool) -> AbsintReport:
    return _run_dbscan(fast, pair=True)


def _audit_morton_sort(fast: bool) -> AbsintReport:
    from repro.core.geometry import scene_bounds
    from repro.core.morton import (morton64, normalize_points,
                                   sort_by_morton64)

    pts = _points()
    return analyze(
        lambda p: sort_by_morton64(*morton64(
            normalize_points(p, *scene_bounds(p)))),
        (pts,), name="morton_sort",
        scale=SymbolicScale(dims=scale_for(N_STAGE, N_SYM)),
        input_ivals=[Ival(0.0, 1.0)])


def _run_sharded(fast: bool, index_dtype, x64: bool) -> AbsintReport:
    import jax
    import jax.numpy as jnp
    from repro.core.distributed import sharded_neighbor_csr

    rng = np.random.default_rng(1)
    pts = jnp.asarray(np.sort(rng.random((N_STAGE, 3), dtype=np.float32),
                              axis=0))
    mesh = jax.make_mesh((1,), ("data",))
    dims = scale_for(N_STAGE, N_SYM,
                     {_SHARD_CAP: AVG_DEGREE * N_SYM,
                      _SHARD_CAP + 1: AVG_DEGREE * N_SYM + 1})
    return analyze(
        lambda p: sharded_neighbor_csr(p, 0.05, capacity=_SHARD_CAP,
                                       mesh=mesh, halo_cap=_HALO_CAP,
                                       index_dtype=index_dtype),
        (pts,),
        name=f"sharded_neighbor_csr[{jnp.dtype(index_dtype).name}]",
        scale=SymbolicScale(dims=dims, axes={"data": N_SHARDS}),
        input_ivals=[Ival(0.0, 1.0)], x64=x64)


def _audit_sharded_int64(fast: bool) -> AbsintReport:
    import jax.numpy as jnp
    return _run_sharded(fast, jnp.int64, x64=True)


def _fixture_sharded_int32(fast: bool) -> AbsintReport:
    import jax.numpy as jnp
    return _run_sharded(fast, jnp.int32, x64=False)


def _fixture_min_image_f32(fast: bool) -> AbsintReport:
    """The paper's periodic-boundary fold applied to the BIG=1e15 ghost
    fill in f32: round() of an operand past 2^24 has ulp spacing > 1, so
    ``round(BIG/L)*L == BIG`` and the fold is an identity (ROADMAP item 3
    trap). The analyzer must derive this from the interval, not from a
    pattern."""
    import jax.numpy as jnp

    L = 100.0

    def min_image(dx):
        # the deliberately-broken twin; the analyzer must rediscover R4's
        # trap from intervals alone  # staticcheck: minimage-ok
        return dx - jnp.round(dx / L) * L

    dx = jnp.zeros((N_STAGE,), jnp.float32)
    return analyze(min_image, (dx,), name="min_image_f32",
                   scale=SymbolicScale(dims=scale_for(N_STAGE, N_SYM)),
                   input_ivals=[Ival(-1.0e15, 1.0e15)])


def _fixture_cancellation(fast: bool) -> AbsintReport:
    """Catastrophic cancellation under a precision floor: subtracting
    overlapping ~1e9-magnitude f32 intervals leaves ~128 absolute error —
    fatal when the caller needs 1e-3 (velocity-dispersion style sums)."""
    import jax.numpy as jnp

    a = jnp.zeros((N_STAGE,), jnp.float32)
    return analyze(lambda x, y: x - y, (a, a), name="cancellation_f32",
                   scale=SymbolicScale(dims=scale_for(N_STAGE, N_SYM),
                                       precision_floor=1e-3),
                   input_ivals=[Ival(1.0e9, 1.1e9), Ival(1.0e9, 1.1e9)])


def _fixture_sentinel_gather(fast: bool) -> AbsintReport:
    """A neighbor list whose "no neighbor" sentinel is ``n`` used directly
    as a gather index: jnp stages PROMISE_IN_BOUNDS, and at symbolic N the
    index interval [0, N] is not inside [0, N-1]. The fix — clip or a
    sentinel-aware where — analyzes clean (see tests/test_absint.py)."""
    import jax.numpy as jnp

    labels = jnp.zeros((N_STAGE,), jnp.int32)
    idx = jnp.zeros((N_STAGE,), jnp.int32)
    return analyze(lambda lab, i: lab[i], (labels, idx),
                   name="sentinel_gather",
                   scale=SymbolicScale(dims=scale_for(N_STAGE, N_SYM)),
                   input_ivals=[Ival(0, 100), Ival(0, N_SYM)])


def _fixture_bad_route(fast: bool) -> AbsintReport:
    """A shard_map halo exchange whose ppermute routes two sources onto
    one destination — not a partial permutation; one shard's halo is
    silently dropped."""
    import jax
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("data",))

    def exchange(x):
        def body(xs):
            return jax.lax.ppermute(xs, "data", [(0, 0), (0, 0)])
        return jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                             out_specs=P("data"), check_vma=False)(x)

    pts = _points()
    return analyze(exchange, (pts,), name="bad_route",
                   scale=SymbolicScale(dims=scale_for(N_STAGE, N_SYM),
                                       axes={"data": N_SHARDS}),
                   input_ivals=[Ival(0.0, 1.0)])


def _audit_wavefront_pallas(fast: bool) -> AbsintReport:
    """The wavefront (Pallas) backend at symbolic N: the padded lane
    bookkeeping around the kernel (arange, pad, slice, the CSR cumsum)
    must prove its index widths like every other path; the pallas_call
    itself is an unknown primitive whose outputs fall back to top —
    soundly silent, never a false positive."""
    from repro.core.query import query_count

    bvh, pred, _ = _csr_args()
    return analyze(
        lambda b, p: query_count(b, p, backend="pallas", sort_queries=True),
        (bvh, pred),
        name="query_count[pallas]",
        scale=SymbolicScale(dims=scale_for(N_STAGE, N_SYM)))


REGISTERED_ABSINT_AUDITS: list[AbsintAudit] = [
    AbsintAudit("query_csr_device/int64", _audit_csr_int64),
    AbsintAudit("query_count/pallas", _audit_wavefront_pallas),
    AbsintAudit("fdbscan", _audit_fdbscan),
    AbsintAudit("fdbscan_pair", _audit_fdbscan_pair),
    AbsintAudit("morton_sort", _audit_morton_sort),
    AbsintAudit("sharded_neighbor_csr/int64", _audit_sharded_int64),
]

# name -> (audit, the one rule that must fire). The int32 configurations
# are real code paths (the pre-PR defaults), not synthetic ASTs: the
# analyzer rediscovers each historical trap from intervals alone.
SEEDED_FIXTURES: list[AbsintAudit] = [
    AbsintAudit("query_csr_device/int32@64e9", _fixture_csr_int32,
                expect_rules=("W1-index-width",)),
    AbsintAudit("sharded_neighbor_csr/int32@64shards", _fixture_sharded_int32,
                expect_rules=("W1-index-width",)),
    AbsintAudit("min_image/f32@BIG", _fixture_min_image_f32,
                expect_rules=("W2-precision",)),
    AbsintAudit("cancellation/f32@floor", _fixture_cancellation,
                expect_rules=("W2-precision",)),
    AbsintAudit("sentinel_gather/unclipped", _fixture_sentinel_gather,
                expect_rules=("W3-bounds",)),
    AbsintAudit("halo_exchange/bad_route", _fixture_bad_route,
                expect_rules=("W3-routes",)),
]


def run_absint_audits(fast: bool = False):
    """Run the registered (clean) audits. Returns ``(findings, reports)``
    where ``findings`` fold into the staticcheck exit code and
    ``reports`` carry the per-entrypoint coverage counters."""
    findings: list[Finding] = []
    reports: list[AbsintReport] = []
    audits = REGISTERED_ABSINT_AUDITS
    if fast:
        # the sharded trace dominates wall time; --fast keeps the rest
        audits = [a for a in audits if not a.name.startswith("sharded")]
    for audit in audits:
        rep = audit.run(fast)
        rep.findings = [f for f in rep.findings
                        if f.rule not in audit.allow]
        reports.append(rep)
        findings.extend(rep.findings)
    return findings, reports


_COVERAGE_CACHE: dict | None = None


def absint_coverage() -> dict:
    """Benchmark-artifact metadata block: one fast registered-audit pass,
    memoized per process. ``seconds: 0.0`` keeps it out of the timing
    gate in ``benchmarks/compare.py`` (records at 0.0 never gate)."""
    global _COVERAGE_CACHE
    if _COVERAGE_CACHE is None:
        findings, reports = run_absint_audits(fast=True)
        _COVERAGE_CACHE = {
            "seconds": 0.0,
            "rules": ["W1-index-width", "W2-precision", "W3-bounds/routes"],
            "entrypoints": [r.name for r in reports],
            "values_analyzed": int(sum(r.values_analyzed for r in reports)),
            "findings": len(findings),
        }
    return dict(_COVERAGE_CACHE)
