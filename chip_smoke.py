#!/usr/bin/env python3
"""Smoke test on the chip: FOF halo finding end to end on one TPU.

Drives the library's main path once, through its public entry points, on
the repo's stand-in for the paper's HACC snapshot (clustered NFW-like
halos in a uniform background, ``benchmarks.common.benchmark_points``)
with random velocities made from ``--seed``:

  fdbscan (FOF: minPts = 2, eps = 0.168 (V/n)^(1/3))
    -> halo_catalog (backend="auto": the Pallas segment kernel on a TPU)
    -> most_bound_centers -> so_masses

and checks what comes out:

  * on 4096 particles from the same generator, the labels match the numpy
    reference ``dbscan_ref`` (``labels_equivalent``, same core points);
  * at full n, the labels are well formed (every label is a core point's
    index and its own root), the catalog counts sum to the clustered
    particles and no overflow flag is set;
  * at full n, ``halo_catalog(backend="pallas")`` matches
    ``backend="jax"`` to 1e-5;
  * the most-bound centers belong to their halos and the SO masses are
    finite.

``--chips 4`` runs only the four-device path: ``halo_pipeline_sharded`` on
a (4,) mesh against ``fdbscan`` + ``halo_catalog`` of the same points on
device 0 (equivalent labels, catalogs equal to 1e-5, no overflow).

  python3 chip_smoke.py              # one chip, default n
  python3 chip_smoke.py --chips 4    # four devices of one host
  JAX_PLATFORMS=cpu python3 chip_smoke.py --n 2048   # rehearsal off chip

Earlier lines report each phase (compile and wall seconds, union rounds,
halos, peak device bytes). The last line is one JSON object,
``{"ok": ..., "device": {"platform", "kind", "count"}}``; ``ok`` is true,
and the exit code 0, only on a TPU with every check passing. Without a TPU
and without ``--n`` the script exits nonzero before running anything.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
# The largest power of two whose smoke finishes well inside 1200 s on one
# v5e. Memory allows 2^21 (compiled fdbscan temporaries 7.6 GB), but every
# traversal pass advances all n lanes in lockstep for as many iterations as
# the longest lane, at about 67 ns per lane-iteration on v5e: one epsilon
# pass at 2^16 takes 28.5 s (longest lane 6501 nodes) and fdbscan 223 s.
DEFAULT_N = 1 << 16
MIN_PTS = 2          # FOF: the paper's minPts
SUBSAMPLE = 4096     # particles checked against the O(n^2) numpy reference
TOL = 1e-5
SO_TOP = 64          # SO masses for the largest halos only
SO_R_MAX = 0.1       # unit box; brackets R200 of the generator's halos
SO_ITERS = 10        # bisection halvings: R200 to SO_R_MAX / 2^10


def _log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Named pass/fail results; the run is ok when there is at least one
    and all passed."""

    def __init__(self):
        self.results: list[tuple[str, bool]] = []

    def add(self, name: str, passed, detail: str = "") -> None:
        self.results.append((name, bool(passed)))
        _log(f"check {name}: {'pass' if passed else 'FAIL'}"
             + (f" ({detail})" if detail else ""))

    @property
    def ok(self) -> bool:
        return bool(self.results) and all(p for _, p in self.results)


def final_status(checks: Checks, device: dict) -> dict:
    return {"ok": device["platform"] == "tpu" and checks.ok, "device": device}


def _aot(fn, *args, **static):
    """Lower and compile a jitted entry point: (compiled, seconds)."""
    t = time.perf_counter()
    compiled = fn.lower(*args, **static).compile()
    return compiled, time.perf_counter() - t


def _timed(fn, *args):
    import jax
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t


def _mem(compiled) -> str:
    m = compiled.memory_analysis()
    if m is None:
        return "memory=not_reported"
    return (f"temp_bytes={m.temp_size_in_bytes} "
            f"arg_bytes={m.argument_size_in_bytes} "
            f"out_bytes={m.output_size_in_bytes}")


def _capacity(num_clusters: int) -> int:
    return max(256, 1 << math.ceil(math.log2(num_clusters + 1)))


def _velocities(n: int, seed: int) -> np.ndarray:
    return (np.random.default_rng((seed, 1)).standard_normal((n, 3))
            .astype(np.float32))


def _catalogs_agree(a, b) -> tuple[bool, str]:
    """Integer fields exactly, float fields to TOL (absolute + relative)."""
    worst = 0.0
    for f in ("num_halos", "overflow", "root", "count", "particle_halo"):
        if not np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))):
            return False, f"{f} differs"
    for f in ("mass", "center", "vmean", "vdisp", "rmax"):
        x = np.asarray(getattr(a, f), np.float64)
        y = np.asarray(getattr(b, f), np.float64)
        if not np.allclose(x, y, rtol=TOL, atol=TOL):
            return False, f"{f} max abs diff {np.abs(x - y).max()}"
        worst = max(worst, float(np.abs(x - y).max(initial=0.0)))
    return True, f"max abs float diff {worst}"


def _labels_well_formed(labels: np.ndarray, core: np.ndarray) -> bool:
    roots = labels[labels >= 0]
    return bool(core[roots].all() and (labels[roots] == roots).all()
                and (labels[core] >= 0).all())


def _peak_bytes() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not_reported"))


def check_subsample(seed: int, n: int, checks: Checks) -> None:
    """fdbscan on up to SUBSAMPLE generator particles vs dbscan_ref."""
    import jax.numpy as jnp
    from benchmarks.common import benchmark_points
    from repro.core.dbscan import fdbscan
    from repro.core.ref_numpy import core_mask_ref, dbscan_ref, labels_equivalent

    m = min(SUBSAMPLE, n)
    pts, eps = benchmark_points(m, seed)
    res = fdbscan(jnp.asarray(pts), eps, MIN_PTS)
    ref_core = core_mask_ref(pts, eps, MIN_PTS)
    ref = dbscan_ref(pts, eps, MIN_PTS)
    got = np.asarray(res.labels)
    checks.add("subsample_labels_vs_dbscan_ref",
               np.array_equal(np.asarray(res.core_mask), ref_core)
               and labels_equivalent(got, ref, ref_core),
               f"n={m} clusters={len(np.unique(got[got >= 0]))}")


def run_one_chip(n: int, seed: int, checks: Checks) -> None:
    """The one-chip main path at ``n`` particles, phase by phase."""
    import jax
    import jax.numpy as jnp
    from benchmarks.common import benchmark_points
    from repro.core.bvh import build_bvh
    from repro.core.dbscan import fdbscan
    from repro.core.geometry import scene_bounds
    from repro.core.query import query_count, within
    from repro.halos import halo_catalog, most_bound_centers, so_masses
    from repro.halos.catalog import _use_pallas

    check_subsample(seed, n, checks)

    pts_np, eps = benchmark_points(n, seed)
    pts = jax.device_put(pts_np)
    vel = jax.device_put(_velocities(n, seed))
    _log(f"phase data: n={n} eps={eps!r} seed={seed}")

    # One ε pass with the traversal counters on: every lockstep pass of the
    # union fixpoint runs as many iterations as its longest lane.
    def traversal_stats(points):
        lo, hi = scene_bounds(points)
        _, st = query_count(build_bvh(points, lo, hi), within(points, eps),
                            with_stats=True)
        return (jnp.max(st.nodes_visited),
                jnp.mean(st.nodes_visited.astype(jnp.float32)),
                jnp.max(st.callback_hits))
    stats, c_s = _aot(jax.jit(traversal_stats), pts)
    (nmax, nmean, hmax), w_s = _timed(stats, pts)
    _log(f"phase traversal_stats: compile_s={c_s!r} wall_s={w_s!r} "
         f"max_nodes_visited={int(nmax)} mean_nodes_visited={float(nmean)!r} "
         f"max_neighbors={int(hmax)}")

    dbscan, c_s = _aot(fdbscan, pts, eps, min_pts=MIN_PTS)
    res, w_s = _timed(dbscan, pts, eps)
    labels = np.asarray(res.labels)
    core = np.asarray(res.core_mask)
    num_clusters = len(np.unique(labels[labels >= 0]))
    _log(f"phase fdbscan: compile_s={c_s!r} wall_s={w_s!r} "
         f"num_rounds={int(res.num_rounds)} clusters={num_clusters} "
         f"noise={int((labels < 0).sum())} {_mem(dbscan)}")
    checks.add("labels_well_formed", _labels_well_formed(labels, core))

    capacity = _capacity(num_clusters)
    native = _use_pallas("auto")
    catalog, c_s = _aot(halo_catalog, pts, vel, res.labels, capacity=capacity,
                        backend="auto")
    kernel_in_hlo = "tpu_custom_call" in catalog.as_text()
    cat, w_s = _timed(catalog, pts, vel, res.labels)
    _log(f"phase halo_catalog: backend=auto -> "
         f"{'pallas' if native else 'jax'} native_kernel={kernel_in_hlo} "
         f"compile_s={c_s!r} wall_s={w_s!r} capacity={capacity} "
         f"num_halos={int(cat.num_halos)} {_mem(catalog)}")
    if jax.default_backend() == "tpu":
        checks.add("catalog_auto_is_native_pallas", native and kernel_in_hlo)
    nh = int(cat.num_halos)
    checks.add("catalog_counts_and_flags",
               not bool(cat.overflow) and nh == num_clusters
               and int(np.asarray(cat.count).sum()) == int((labels >= 0).sum()),
               f"halos={nh} members={int((labels >= 0).sum())}")

    other = "jax" if native else "pallas"
    cat_o, w_s = _timed(lambda p, v, lab: halo_catalog(
        p, v, lab, capacity=capacity, backend=other), pts, vel, res.labels)
    agree, detail = _catalogs_agree(cat, cat_o)
    _log(f"phase halo_catalog_{other}: wall_s={w_s!r} (compile included)")
    checks.add("catalog_pallas_vs_jax", agree, detail)

    mb, w_s = _timed(lambda p, ph: most_bound_centers(
        p, ph, eps, capacity=capacity), pts, cat.particle_halo)
    idx = np.asarray(mb.index)[:nh]
    ph = np.asarray(cat.particle_halo)
    _log(f"phase most_bound_centers: wall_s={w_s!r} (compile included)")
    checks.add("most_bound_in_own_halo",
               (idx >= 0).all() and (ph[idx] == np.arange(nh)).all()
               and np.isfinite(np.asarray(mb.center)).all())

    top = np.argsort(-np.asarray(cat.count), kind="stable")[:min(SO_TOP, nh)]
    so, w_s = _timed(lambda p, c: so_masses(
        p, c, jnp.ones((c.shape[0],), bool), r_max=SO_R_MAX, iters=SO_ITERS),
        pts, mb.center[jnp.asarray(top)])
    r = np.asarray(so.r_delta)
    _log(f"phase so_masses: wall_s={w_s!r} (compile included) halos={len(top)} "
         f"bracketed={int(np.asarray(so.bracketed).sum())} "
         f"largest_m200={float(np.asarray(so.m_delta).max(initial=0.0))!r}")
    checks.add("so_masses_finite",
               np.isfinite(r).all() and (r >= 0).all()
               and (np.asarray(so.count) <= n).all())
    _log(f"peak_bytes_in_use={_peak_bytes()}")


def _ghost_capacity(pts: np.ndarray, eps: float, shards: int) -> int:
    """The most boundary points any slab ships to a neighbor, rounded up to
    1024: the halo_cap that holds every ghost at this n."""
    e = np.float32(eps)
    worst = 0
    for x in np.split(pts[:, 0], shards):
        worst = max(worst, int((x <= x.min() + e).sum()),
                    int((x >= x.max() - e).sum()))
    return -(-worst // 1024) * 1024


def run_four_chips(n: int, seed: int, checks: Checks) -> None:
    """halo_pipeline_sharded on a (4,) mesh vs the one-device path."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from benchmarks.common import benchmark_points
    from repro.core.distributed import slab_partition
    from repro.core.dbscan import fdbscan
    from repro.core.ref_numpy import labels_equivalent
    from repro.halos import halo_catalog, halo_pipeline_sharded

    devs = jax.devices()
    if len(devs) < 4:
        checks.add("four_devices", False, f"found {len(devs)}")
        return
    pts_np, eps = benchmark_points(n, seed)
    pts_np, order = slab_partition(pts_np, 4)
    vel_np = _velocities(n, seed)[order]
    halo_cap = _ghost_capacity(pts_np, eps, 4)
    _log(f"phase data: n={n} eps={eps!r} seed={seed} shards=4 "
         f"halo_cap={halo_cap}")

    p0 = jax.device_put(pts_np, devs[0])
    v0 = jax.device_put(vel_np, devs[0])
    dbscan, c_s = _aot(fdbscan, p0, eps, min_pts=MIN_PTS)
    ref, w_s = _timed(dbscan, p0, eps)
    ref_labels = np.asarray(ref.labels)
    num_clusters = len(np.unique(ref_labels[ref_labels >= 0]))
    capacity = _capacity(num_clusters)
    _log(f"phase fdbscan_device0: compile_s={c_s!r} wall_s={w_s!r} "
         f"num_rounds={int(ref.num_rounds)} clusters={num_clusters}")
    ref_cat, w_s = _timed(lambda p, v, lab: halo_catalog(
        p, v, lab, capacity=capacity, min_count=MIN_PTS), p0, v0, ref.labels)
    _log(f"phase halo_catalog_device0: wall_s={w_s!r} (compile included) "
         f"num_halos={int(ref_cat.num_halos)}")

    mesh = jax.make_mesh((4,), ("data",), devices=devs[:4],
                         axis_types=(jax.sharding.AxisType.Auto,))
    rows = NamedSharding(mesh, P("data", None))
    ps = jax.device_put(pts_np, rows)
    vs = jax.device_put(vel_np, rows)
    res, w_s = _timed(lambda p, v: halo_pipeline_sharded(
        p, v, eps, MIN_PTS, mesh=mesh, capacity=capacity, halo_cap=halo_cap,
        min_count=MIN_PTS), ps, vs)
    _log(f"phase halo_pipeline_sharded: wall_s={w_s!r} (compile included) "
         f"rounds={int(res.rounds)} num_halos={int(res.catalog.num_halos)}")
    checks.add("sharded_no_overflow",
               not bool(res.halo_overflow) and not bool(res.catalog.overflow)
               and not bool(ref_cat.overflow))
    checks.add("sharded_labels_equivalent",
               np.array_equal(np.asarray(res.core_mask),
                              np.asarray(ref.core_mask))
               and labels_equivalent(np.asarray(res.labels), ref_labels,
                                     np.asarray(ref.core_mask)))
    agree, detail = _catalogs_agree(res.catalog, ref_cat)
    checks.add("sharded_catalog_vs_device0", agree, detail)
    _log(f"peak_bytes_in_use_device0={_peak_bytes()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=None,
                    help=f"particles (default {DEFAULT_N}; required off a TPU)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded pipeline vs device 0")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chip_smoke.py: src/repro not found next to this script; run "
              "it from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from benchmarks.common import device_record, enable_compile_cache

    cache = enable_compile_cache()
    cache_warm = os.path.isdir(cache) and bool(os.listdir(cache))
    device = device_record()
    if device["platform"] != "tpu" and args.n is None:
        print(f"chip_smoke.py: JAX found no TPU (platform "
              f"{device['platform']!r}); pass --n for a small rehearsal",
              file=sys.stderr)
        return 2
    n = args.n or DEFAULT_N
    _log(f"device platform={device['platform']} kind={device['kind']!r} "
         f"count={device['count']} chips_path={args.chips} "
         f"compile_cache={cache} cache_warm={cache_warm}")
    checks = Checks()
    t0 = time.perf_counter()
    try:
        (run_four_chips if args.chips == 4 else run_one_chip)(
            n, args.seed, checks)
    except Exception:  # noqa: BLE001 — any failed phase fails the smoke
        traceback.print_exc()
        checks.add("all_phases_ran", False)
    _log(f"total_s={time.perf_counter() - t0!r}")
    status = final_status(checks, device)
    print(json.dumps(status), flush=True)
    return 0 if status["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
