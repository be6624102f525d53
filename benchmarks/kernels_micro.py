"""Microbenchmarks for the Pallas kernels and their jnp references.

The kernels compile natively on a TPU and run in interpret mode anywhere
else (``kernels.pairwise.INTERPRET``); interpret-mode times measure the
Pallas interpreter, not a chip, so they are relative numbers only. The
``kernels/mode`` record names the device and the mode, so a baseline can
never silently mix the two. The wavefront traversal has no native
lowering yet (``kernels.wavefront.LOWERING_GAP``), so on a TPU its record
says it was not run.

Emits the usual CSV lines plus a ``BENCH_kernels.json`` artifact (kernel
and reference timings per size) for the ``benchmarks.compare``
regression gate.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.kernels import ops, ref, segment
from repro.kernels.pairwise import INTERPRET
from benchmarks.common import (benchmark_points, device_record, emit, timeit,
                               write_artifact)


def _mode_record() -> dict:
    # seconds pinned at 0.0: compare never gates on this record, it only
    # documents how the numbers alongside it were produced.
    return {"seconds": 0.0, "interpret": INTERPRET, "device": device_record()}


def _bench_pairwise(results: dict) -> None:
    rng = np.random.default_rng(0)
    for n, d in ((1024, 3), (1024, 64), (4096, 3)):
        x = jnp.asarray(rng.uniform(0, 1, (n, d)), jnp.float32)
        eps = 0.1
        t_ref = timeit(lambda: ref.pairwise_count_ref(x, x, eps * eps))
        t_k = timeit(lambda: ops.eps_neighbor_counts(x, x, eps))
        got = np.asarray(ops.eps_neighbor_counts(x, x, eps))
        want = np.asarray(ref.pairwise_count_ref(x, x, eps * eps))
        # pairs within ~1e-5 relative of eps are float knife-edges: the
        # kernel's expanded-form distance can round across the threshold.
        mismatch = int((got != want).sum())
        assert mismatch <= max(4, n // 1000), (n, d, mismatch)
        emit(f"kernel_pairwise_count_n{n}_d{d}", t_k,
             f"ref_us={t_ref * 1e6:.1f};knife_edge_rows={mismatch}")
        results[f"kernels/pairwise_count_n{n}_d{d}"] = {
            "seconds": t_k, "n": n, "d": d,
            "ref_seconds": t_ref, "knife_edge_rows": mismatch}


def _bench_segment(results: dict) -> None:
    rng = np.random.default_rng(1)
    for n, nseg in ((4096, 64),):
        seg = np.sort(rng.integers(0, nseg, n)).astype(np.int32)
        data = jnp.asarray(rng.normal(size=(n, 4)), jnp.float32)
        seg = jnp.asarray(seg)
        t_k = timeit(lambda: segment.segment_sum_sorted(data, seg, nseg))
        t_ref = timeit(lambda: ref.segment_sum_sorted_ref(data, seg, nseg))
        got = np.asarray(segment.segment_sum_sorted(data, seg, nseg))
        want = np.asarray(ref.segment_sum_sorted_ref(data, seg, nseg))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        emit(f"kernel_segment_sum_n{n}_s{nseg}", t_k,
             f"ref_us={t_ref * 1e6:.1f}")
        results[f"kernels/segment_sum_n{n}_s{nseg}"] = {
            "seconds": t_k, "n": n, "segments": nseg, "ref_seconds": t_ref}


def _bench_wavefront(results: dict) -> None:
    from repro.core.bvh import build_bvh
    from repro.core.geometry import scene_bounds
    from repro.core.query import query_count, within

    n = 1024
    if not INTERPRET:
        emit(f"kernel_wavefront_count_n{n}", 0.0, "not_run=no_native_lowering")
        results[f"kernels/wavefront_count_n{n}"] = {
            "seconds": 0.0, "n": n, "not_run": "no native lowering"}
        return
    pts, eps = benchmark_points(n)
    jp = jnp.asarray(pts)
    lo, hi = scene_bounds(jp)
    bvh = build_bvh(jp, lo, hi)
    pred = within(jp, eps)
    t_k = timeit(lambda: query_count(bvh, pred, backend="pallas",
                                     sort_queries=True), iters=2)
    t_ref = timeit(lambda: query_count(bvh, pred, backend="stackless",
                                       sort_queries=True), iters=2)
    emit(f"kernel_wavefront_count_n{n}", t_k, f"ref_us={t_ref * 1e6:.1f}")
    results[f"kernels/wavefront_count_n{n}"] = {
        "seconds": t_k, "n": n, "ref_seconds": t_ref}


def main(out_path: str = "BENCH_kernels.json") -> None:
    results: dict = {"kernels/mode": _mode_record()}
    _bench_pairwise(results)
    _bench_segment(results)
    _bench_wavefront(results)
    write_artifact(out_path, results)


if __name__ == "__main__":
    main()
