"""Sharded-pipeline benchmark: the end-to-end on-device query path.

Times the three layers of the device-resident multi-shard stack over this
process's devices (``jax.devices()``, up to 4; 2 with ``--fast``):

  * ``sharded_neighbor_csr`` — build → ghost exchange → device CSR,
  * ``dbscan_distributed``   — + engine-traversal DBSCAN fixpoint,
  * ``halo_pipeline_sharded`` — + catalog merge (the full fused region).

Alongside wall times it records what the device-resident protocol buys:

  * host syncs per CSR query: two-pass = 1 (the sizing ``int()``), buffered =
    measured retry attempts, device-resident = 0;
  * CSR staging memory on a SKEWED neighborhood distribution (one query
    matching everything): the dense staging a (q × max_count) gather would
    need vs. the device protocol's ``capacity + (q+1) + q·chunk`` words.

With fewer than 2 devices it records that it was not run. On a CPU host,
give the process virtual devices before JAX starts:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      PYTHONPATH=src python -m benchmarks.distributed_pipeline [--fast]

Emits CSV lines plus a ``BENCH_distributed.json`` artifact.
"""
from __future__ import annotations

import argparse

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import (benchmark_points, device_record, emit, timeit,
                               write_artifact)


def _stage_times(mesh, ndev: int, n: int, trace_path: str) -> dict:
    from repro.core.distributed import (dbscan_distributed, slab_partition,
                                        sharded_neighbor_csr)
    from repro.halos import halo_pipeline_sharded

    pts, eps = benchmark_points(n)
    pts, _ = slab_partition(pts, ndev)
    jp = jnp.asarray(pts)
    vel = jnp.asarray(np.random.default_rng(1)
                      .standard_normal((n, 3)).astype(np.float32))

    out = {}
    out["neighbor_csr"] = timeit(lambda: sharded_neighbor_csr(
        jp, eps, capacity=32 * n, mesh=mesh, halo_cap=n).indices, iters=2)
    out["dbscan"] = timeit(lambda: dbscan_distributed(
        jp, eps, 2, mesh=mesh, halo_cap=n).labels, iters=2)
    out["pipeline"] = timeit(lambda: halo_pipeline_sharded(
        jp, vel, eps, 2, mesh=mesh, capacity=n, halo_cap=n,
        min_count=2).labels, iters=2)

    # buffered-protocol retry count on the same local problem (the only
    # protocol whose host-sync count is data-dependent).
    from repro.core.bvh import build_bvh
    from repro.core.geometry import scene_bounds
    from repro.core.query import query_csr_buffered, within
    lo, hi = scene_bounds(jp)
    bvh = build_bvh(jp, lo, hi)
    buf = query_csr_buffered(bvh, within(jp, eps), capacity=8)
    out["buffered_attempts"] = int(buf.attempts)

    # One traced pass through both entry points: fenced spans around the
    # fused launch + per-stage spans from the staged pipeline, exported as
    # Chrome-trace JSON (load in ui.perfetto.dev).
    from repro.obs import SpanTracer
    from repro.halos.merge import halo_pipeline_traced
    tracer = SpanTracer(process_name="distributed_pipeline")
    sharded_neighbor_csr(jp, eps, capacity=32 * n, mesh=mesh, halo_cap=n,
                         tracer=tracer)
    halo_pipeline_traced(jp, vel, eps, 2, mesh=mesh, capacity=n,
                         halo_cap=n, min_count=2, tracer=tracer)
    tracer.export(trace_path)
    out["trace_spans"] = sum(1 for e in tracer.events if e["ph"] == "X")
    return out


def _staging_words(q: int, max_count: int, capacity: int, chunk: int) -> dict:
    """Analytic CSR staging footprint (int32 words) for a q-query batch."""
    return {
        "dense_gather": q * max_count,
        "device_csr": capacity + (q + 1) + q * chunk,
    }


def main(fast: bool = False, out_path: str = "BENCH_distributed.json",
         trace_path: str = "trace_distributed.json") -> None:
    ndev = min(len(jax.devices()), 2 if fast else 4)
    n = 256 if fast else 1024
    if ndev < 2:
        emit("distributed/pipeline", 0.0, derived="not_run=1_device")
        write_artifact(out_path, {"distributed/not_run": {
            "seconds": 0.0, "reason": "not run on 1 device",
            "device": device_record()}})
        return
    mesh = jax.make_mesh((ndev,), ("data",), devices=jax.devices()[:ndev],
                         axis_types=(jax.sharding.AxisType.Auto,))
    stages = _stage_times(mesh, ndev, n, trace_path)

    results: dict = {}
    for stage in ("neighbor_csr", "dbscan", "pipeline"):
        t = stages[stage]
        name = f"distributed/{stage}_n{n}_s{ndev}"
        emit(name, t, derived=f"shards={ndev};points_per_s={n / max(t, 1e-12):.0f}")
        results[name] = {"seconds": t, "n": n, "shards": ndev, "stage": stage}

    # host syncs per CSR query, by output protocol
    syncs = {"two_pass": 1, "buffered": stages["buffered_attempts"], "device": 0}
    for proto, k in syncs.items():
        emit(f"distributed/host_syncs_{proto}", 0.0, derived=f"syncs={k}")
    results["distributed/host_syncs"] = syncs

    # skewed vs uniform staging memory (words), q = n queries
    cap, chunk = 32 * n, 32
    skew = _staging_words(q=n, max_count=n, capacity=cap, chunk=chunk)
    unif = _staging_words(q=n, max_count=64, capacity=cap, chunk=chunk)
    for label, w in (("skewed", skew), ("uniform", unif)):
        emit(f"distributed/staging_{label}", 0.0,
             derived=f"dense_words={w['dense_gather']};"
                     f"device_words={w['device_csr']}")
    results["distributed/staging_words"] = {"skewed": skew, "uniform": unif}

    emit("distributed/trace_spans", 0.0,
         derived=f"spans={stages['trace_spans']};file={trace_path}")

    results["distributed/device"] = {"seconds": 0.0, **device_record()}
    write_artifact(out_path, results)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    main(fast=args.fast)
