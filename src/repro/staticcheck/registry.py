"""Registered jaxpr audits: the repo's device pipelines, each traced and
checked against the Layer-1 rules.

One entry per audited entry point (the CSR device path, the DBSCAN
variants, the fused sharded halo pipeline, the Pallas kernel wrappers,
and the serving tier's fixed-bucket recompile premise). The registry is
consumed two ways:

* ``pytest`` — ``tests/test_staticcheck.py`` parametrizes one test per
  audit, so a regression names the entry point that broke;
* the CLI — ``python -m repro.staticcheck --jaxpr [--fast]`` runs them
  all and folds the findings into the JSON report.

Budgets are sized per entry point as "the dense object this pipeline
must NOT stage": ``q x max_count`` for CSR fills, ``n x n`` for
neighbor pipelines.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

from repro.staticcheck.findings import Finding
from repro.staticcheck.jaxpr_audit import (audit_jaxpr, bounded_recompiles,
                                           jaxpr_op_signature,
                                           no_dense_intermediate,
                                           no_host_transfer)

__all__ = ["Audit", "REGISTERED_AUDITS", "run_registered_audits"]


@dataclasses.dataclass(frozen=True)
class Audit:
    name: str
    run: Callable[[bool], list[Finding]]  # fast -> findings


def _skewed_workload(n: int, nq: int):
    """One fat query matching every point, the rest matching none — the
    workload where a dense ``(q, max_count)`` fill buffer is maximal."""
    import jax.numpy as jnp
    from repro.core.bvh import build_bvh
    from repro.core.geometry import scene_bounds
    from repro.core.query import within

    rng = np.random.default_rng(3)
    pts = jnp.asarray(rng.uniform(0, 1, (n, 3)).astype(np.float32))
    lo, hi = scene_bounds(pts)
    bvh = build_bvh(pts, lo, hi)
    queries = np.full((nq, 3), 50.0, np.float32)
    queries[0] = 0.5
    radii = np.full((nq,), 1e-3, np.float32)
    radii[0] = 2.0
    pred = within(jnp.asarray(queries), jnp.asarray(radii))
    return bvh, pred


def _audit_query_csr_device(fast: bool) -> list[Finding]:
    from repro.core.query import query_csr_device

    n = nq = 128 if fast else 256
    bvh, pred = _skewed_workload(n, nq)
    dense = nq * n  # the forbidden (q, max_count) buffer
    return audit_jaxpr(
        lambda b, p: query_csr_device(b, p, capacity=n + 64, chunk=16),
        (bvh, pred),
        [no_dense_intermediate(dense), no_host_transfer()],
        name="query_csr_device")


def _clustered(n: int):
    import jax.numpy as jnp
    from repro.data.pipeline import hacc_benchmark_epsilon, make_clustered_points

    pts = make_clustered_points(np.random.default_rng(0), n)
    eps = hacc_benchmark_epsilon(1.0, n)
    return jnp.asarray(pts), float(eps)


def _audit_fdbscan(fast: bool) -> list[Finding]:
    from repro.core.dbscan import fdbscan

    n = 128 if fast else 512
    pts, eps = _clustered(n)
    return audit_jaxpr(
        lambda p: fdbscan(p, eps, 2), (pts,),
        [no_dense_intermediate(n * n), no_host_transfer()],
        name="fdbscan")


def _audit_fdbscan_pair(fast: bool) -> list[Finding]:
    from repro.core.dbscan import fdbscan_pair

    n = 128 if fast else 512
    pts, eps = _clustered(n)
    return audit_jaxpr(
        lambda p: fdbscan_pair(p, eps, 2), (pts,),
        [no_dense_intermediate(n * n), no_host_transfer()],
        name="fdbscan_pair")


def _audit_halo_pipeline_sharded(fast: bool) -> list[Finding]:
    import jax
    import jax.numpy as jnp
    from repro.halos import halo_pipeline_sharded

    n = 128 if fast else 256
    # One device: the audit reads the staged jaxpr, and a process that has
    # forced hundreds of host devices would not divide n.
    mesh = jax.make_mesh((1,), ("data",), devices=jax.devices()[:1],
                         axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(7)
    pts = np.sort(rng.uniform(0, 1, (n, 3)).astype(np.float32), axis=0)
    vel = rng.standard_normal((n, 3)).astype(np.float32)
    return audit_jaxpr(
        lambda p, v: halo_pipeline_sharded(
            p, v, 0.05, 2, mesh=mesh, capacity=64, halo_cap=64, min_count=2),
        (jnp.asarray(pts), jnp.asarray(vel)),
        [no_dense_intermediate(n * n), no_host_transfer()],
        name="halo_pipeline_sharded")


def _audit_kernel_pairwise(fast: bool) -> list[Finding]:
    import jax.numpy as jnp
    from repro.kernels import ops

    m = n = 256 if fast else 512
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.uniform(0, 1, (m, 3)), jnp.float32)
    # budget: the full (m, n) pairwise mask — the kernel must stay tiled
    return audit_jaxpr(
        lambda a: ops.eps_neighbor_counts(a, a, 0.1), (x,),
        [no_dense_intermediate(m * n), no_host_transfer()],
        name="eps_neighbor_counts")


def _audit_serving_buckets(fast: bool) -> list[Finding]:
    """The serving tier's fixed-bucket premise (ROADMAP item 4): a sweep of
    arbitrary request sizes, padded to power-of-two buckets, must hit a
    bounded number of compiled shapes."""
    import jax.numpy as jnp
    from repro.core.bvh import build_bvh
    from repro.core.geometry import scene_bounds
    from repro.core.query import query_count, within

    n = 64
    rng = np.random.default_rng(11)
    pts = jnp.asarray(rng.uniform(0, 1, (n, 3)).astype(np.float32))
    lo, hi = scene_bounds(pts)
    bvh = build_bvh(pts, lo, hi)

    def bucketed(nq: int):
        cap = 1 << max(2, (nq - 1).bit_length())   # next power of two, >= 4
        q = np.full((cap, 3), 50.0, np.float32)    # pad with far-away queries
        q[:nq] = rng.uniform(0, 1, (nq, 3)).astype(np.float32)
        return (jnp.asarray(q),)

    sizes = [1, 2, 3, 4, 5, 7, 8] if fast else list(range(1, 33))
    sweep = [bucketed(nq) for nq in sizes]
    cap = 3 if fast else 5  # buckets {4, 8} fast; {4, 8, 16, 32} full
    return bounded_recompiles(
        lambda q: query_count(bvh, within(q, 0.1)), sweep, cap,
        name="serving_bucketed_query")


def _audit_stats_path_identity(fast: bool) -> list[Finding]:
    """The obs layer's zero-cost contract: with ``with_stats=False`` the
    engine must stage the exact pre-obs program. Compares the live
    ``query_count`` path against the frozen twin snapshot in
    ``staticcheck/frozen_query.py`` by op-level jaxpr signature, for both
    instrumented traversal cores (rope + stack)."""
    from repro.core.query import query_count
    from repro.staticcheck.frozen_query import (frozen_count_stack,
                                                frozen_count_stackless)

    n, nq = (128, 32) if fast else (256, 64)
    bvh, pred = _skewed_workload(n, nq)
    findings: list[Finding] = []
    for backend, frozen in (("stackless", frozen_count_stackless),
                            ("stack", frozen_count_stack)):
        live = jaxpr_op_signature(
            lambda b, p: query_count(b, p, backend=backend), (bvh, pred))
        ref = jaxpr_op_signature(frozen, (bvh, pred))
        if live == ref:
            continue
        divergence = next(
            (i for i, (a, b) in enumerate(zip(live, ref)) if a != b),
            min(len(live), len(ref)))
        findings.append(Finding(
            rule="stats-path-identity",
            path=f"<jaxpr:query_count[{backend}]>", line=0,
            message=(
                f"with_stats=False path diverged from the frozen pre-obs "
                f"jaxpr at op {divergence} "
                f"(live {len(live)} ops vs frozen {len(ref)}; "
                f"live[{divergence}]="
                f"{live[divergence] if divergence < len(live) else '<end>'}, "
                f"frozen[{divergence}]="
                f"{ref[divergence] if divergence < len(ref) else '<end>'}): "
                f"counter arithmetic is leaking into the stats-off hot "
                f"path, or the engine changed without updating "
                f"staticcheck/frozen_query.py")))
    return findings


def _audit_obs_stats(fast: bool) -> list[Finding]:
    """The stats-ON entry points under the existing device-discipline
    rules: instrumented traversal must still stage no host transfer and no
    dense buffer (the counters ride the loop carry)."""
    from repro.core.query import query_count

    n, nq = (128, 32) if fast else (256, 64)
    bvh, pred = _skewed_workload(n, nq)
    findings: list[Finding] = []
    for backend in ("stackless", "stack"):
        findings.extend(audit_jaxpr(
            lambda b, p: query_count(b, p, backend=backend, with_stats=True),
            (bvh, pred),
            [no_dense_intermediate(nq * n), no_host_transfer()],
            name=f"query_count_stats_{backend}"))
    return findings


def _audit_wavefront_backend(fast: bool) -> list[Finding]:
    """backend='pallas' under the device-discipline rules: the wavefront
    count pass and the resumable chunked CSR fill must stage no host
    transfer and no dense buffer — the audit walker descends into the
    pallas_call kernel jaxpr, so the kernel body is covered too."""
    from repro.core.query import query_count, query_csr_device

    n = nq = 128 if fast else 256
    bvh, pred = _skewed_workload(n, nq)
    dense = nq * n
    findings = audit_jaxpr(
        lambda b, p: query_count(b, p, backend="pallas"),
        (bvh, pred),
        [no_dense_intermediate(dense), no_host_transfer()],
        name="query_count_pallas")
    findings += audit_jaxpr(
        lambda b, p: query_csr_device(b, p, capacity=n + 64, chunk=16,
                                      backend="pallas"),
        (bvh, pred),
        [no_dense_intermediate(dense), no_host_transfer()],
        name="query_csr_device_pallas")
    return findings


REGISTERED_AUDITS: list[Audit] = [
    Audit("query_csr_device", _audit_query_csr_device),
    Audit("kernels/wavefront_backend", _audit_wavefront_backend),
    Audit("fdbscan", _audit_fdbscan),
    Audit("fdbscan_pair", _audit_fdbscan_pair),
    Audit("halo_pipeline_sharded", _audit_halo_pipeline_sharded),
    Audit("kernels/eps_neighbor_counts", _audit_kernel_pairwise),
    Audit("serving/bucketed_recompiles", _audit_serving_buckets),
    Audit("obs/stats_path_identity", _audit_stats_path_identity),
    Audit("obs/query_stats_device", _audit_obs_stats),
]


def run_registered_audits(fast: bool = False) -> tuple[list[Finding], list[str]]:
    findings: list[Finding] = []
    names: list[str] = []
    for audit in REGISTERED_AUDITS:
        names.append(audit.name)
        findings.extend(audit.run(fast))
    return findings, names
