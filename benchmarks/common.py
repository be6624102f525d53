"""Shared benchmark utilities: timing, the benchmark dataset (paper §4),
and the ``BENCH_*.json`` artifact writer for the regression gate."""
from __future__ import annotations

import json
import os
import pathlib
import time

import numpy as np
import jax

from repro.data.pipeline import hacc_benchmark_epsilon, make_clustered_points


def timeit(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall seconds over iters (after jit warmup)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def benchmark_points(n: int, seed: int = 0) -> tuple[np.ndarray, float]:
    """The paper's benchmark problem, downscaled: clustered NFW-like points
    in the unit box with ε = b (V/n)^{1/3}, b = 0.168 (paper footnote 1).
    The paper's snapshot is 37M points on an A100; CPU benches use n ≤ ~10^5
    with the SAME ε convention so the density regime matches."""
    pts = make_clustered_points(np.random.default_rng(seed), n)
    eps = hacc_benchmark_epsilon(1.0, n)
    return pts, eps


def emit(name: str, seconds: float, derived: str = "") -> None:
    print(f"{name},{seconds * 1e6:.1f},{derived}", flush=True)


def write_artifact(out_path: str, results: dict) -> None:
    """Write a ``BENCH_*.json`` artifact for ``benchmarks.compare``.

    Keep every field inside a record that carries ``seconds``:
    ``compare`` tolerance-bands the ``seconds`` value and ignores the rest,
    while a record WITHOUT ``seconds`` becomes an exact-match contract —
    too brittle for anything derived from timings or platform specifics.
    """
    pathlib.Path(out_path).write_text(json.dumps(results, indent=2))


def device_record() -> dict:
    """The device a measurement ran on, as JAX reports it."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for a script's process.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set. Otherwise the cache lives at ``<repo>/.jax_cache``:
    a fixed path, since the path is part of each entry's key. Call it from
    a script's ``main``; importing a library module never turns it on."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(pathlib.Path(__file__).resolve().parent.parent / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
