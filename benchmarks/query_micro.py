"""Query-engine micro-benchmark: backend × output-protocol grid.

Times the unified engine (core/query.py) on the paper's benchmark problem
so the cost of each output protocol is tracked per backend:

  protocols: fused-callback count (the §4.1.1 baseline: no storage),
             two-pass count-then-fill CSR (§4.1; one sizing host sync),
             device-resident scan-then-scatter CSR (fixed capacity,
             zero host syncs — the ArborX 2.0 contract),
             single-pass buffered CSR (the §4.1 buffer optimization —
             timed with a capacity that holds, i.e. the zero-retry
             common case),
  backends:  stackless (rope), stack, and the Pallas wavefront kernel
             (interpret mode off the TPU — the column tracks dispatch/
             padding overhead there; it has no native lowering yet, so a
             TPU run leaves it out), plus the pair backend's fused count
             for the self-join workloads.

Emits the usual CSV lines plus a ``BENCH_query.json`` artifact so CSR
two-pass vs. fused-callback cost rides along the existing benches.

  PYTHONPATH=src python -m benchmarks.query_micro [--fast]
"""
from __future__ import annotations

import argparse

import jax.numpy as jnp
import numpy as np

from benchmarks.common import benchmark_points, emit, timeit, write_artifact
from repro.core.bvh import build_bvh
from repro.core.geometry import scene_bounds
from repro.core.query import (query, query_count, query_csr,
                              query_csr_buffered, query_csr_device, within)
from repro.kernels.pairwise import INTERPRET


def _grid(n: int, results: dict) -> None:
    pts, eps = benchmark_points(n)
    jp = jnp.asarray(pts)
    lo, hi = scene_bounds(jp)
    bvh = build_bvh(jp, lo, hi)
    pred = within(jp, eps)
    max_count = int(jnp.max(query_count(bvh, pred)))
    # a capacity the buffered pass never overflows at: the zero-retry case
    cap0 = 1 << max(1, int(np.ceil(np.log2(max_count))))

    def pair_count():
        def cb(c, i, j, d2):
            return c + 1, jnp.bool_(False)
        return query(bvh, pred, cb, jnp.int32(0), backend="pair")

    backends = ("stackless", "stack") + (("pallas",) if INTERPRET else ())
    runs = [("count", b, lambda b=b: query_count(bvh, pred, backend=b))
            for b in backends]
    runs += [("csr_two_pass", b,
              lambda b=b: query_csr(bvh, pred, backend=b).indices)
             for b in backends]
    # device-resident CSR: fixed capacity, no host sync anywhere
    cap_dev = n * cap0
    runs += [("csr_device", b,
              lambda b=b: query_csr_device(bvh, pred, cap_dev,
                                           backend=b).indices)
             for b in backends]
    runs += [("csr_buffered", b,
              lambda b=b: query_csr_buffered(bvh, pred, capacity=cap0,
                                             backend=b).indices)
             for b in backends]
    runs.append(("count", "pair", pair_count))

    for protocol, backend, fn in runs:
        t = timeit(fn, iters=2)
        name = f"query/{protocol}_{backend}_n{n}"
        emit(name, t, derived=f"max_count={max_count};"
                              f"queries_per_s={n / max(t, 1e-12):.0f}")
        results[name] = {"seconds": t, "n": n, "protocol": protocol,
                         "backend": backend, "max_count": max_count}


def main(fast: bool = False, out_path: str = "BENCH_query.json") -> None:
    results: dict = {}
    for n in ([512] if fast else [2048, 8192]):
        _grid(n, results)
    write_artifact(out_path, results)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    main(fast=args.fast)
