"""The analysis step of HACC in-situ FOF halo finding on one chip.

Particles in HBM to a finished catalog, through the library's entry points:

  fdbscan (FOF: minPts, eps = b (V/n)^(1/3))
    -> halo_catalog(backend="auto")
    -> most_bound_centers, then so_masses of the ``so_halos`` largest halos

Each stage is one AOT-compiled program, fenced at its end. A step module
provides ``make(cfg, mix, chips, devices)``, whose object has:

* ``particles_per_device``;
* ``place(snapshot)`` and ``compile(placed)`` (set-up);
* ``run(placed, stages)`` (one step; ``stages(name, program, *args)`` runs
  and fences one program), ``counters(out)`` and ``fetch(out)``;
* ``reference(snapshot)``, ``check(snapshot, host, ref)`` -> the numbers
  compared, and ``control(snapshot)``, the reference in the precision below
  the configuration's, in the form ``fetch`` gives;
* ``probe(placed, out, snapshot, trace_call)`` and
  ``probe_reference(snapshot)``: the measurements of a traced run after its
  window; ``trace_call(name, program, *args)`` runs a program once under a
  profiler trace of its own and returns its output and the trace's
  reduction; ``release()``.
"""
from __future__ import annotations

import time

import numpy as np

from bench import reference as ref_lib

__all__ = ["make"]


def _timed_mean(fn, *args, min_s: float = 0.3):
    """Mean seconds of fenced calls of a compiled program, repeated until
    together they span ``min_s`` (the host clock is good to about 0.5 ms)."""
    import jax

    reps, total, out = 0, 0.0, None
    while total < min_s:
        t = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        total += time.perf_counter() - t
        reps += 1
    return total / reps, out


def _bf16(x):
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


class FofStep:
    def __init__(self, cfg, mix, chips, devices):
        self.cfg = cfg
        self.device = devices[0]
        self.min_pts = int(cfg["min_pts"])
        self.capacity = int(cfg["catalog_capacity"])
        self.so = cfg["so"]
        self.particles_per_device = int(mix["particles_per_chip"]) * chips
        self.programs = {}

    # --- set-up ------------------------------------------------------------
    def place(self, snap):
        import jax

        return (jax.device_put(snap.points, self.device),
                jax.device_put(snap.velocities, self.device),
                np.float32(snap.eps))

    def _stage_fns(self):
        import jax
        import jax.numpy as jnp

        from repro.core.dbscan import fdbscan
        from repro.halos import halo_catalog, most_bound_centers, so_masses

        min_pts, cap, so = self.min_pts, self.capacity, self.so

        def fof(points, eps):
            return fdbscan(points, eps, min_pts)

        def catalog(points, velocities, labels):
            return halo_catalog(points, velocities, labels, capacity=cap,
                                min_count=min_pts, backend="auto")

        def props(points, eps, particle_halo, count):
            mb = most_bound_centers(points, particle_halo, eps, capacity=cap)
            _, top = jax.lax.top_k(count, int(so["halos"]))
            res = so_masses(points, mb.center[top], count[top] > 0,
                            delta=float(so["delta"]),
                            r_max=float(so["r_max"]), iters=int(so["iters"]))
            return mb, top.astype(jnp.int32), res

        return {"fdbscan": fof, "halo_catalog": catalog, "halo_props": props}

    def compile(self, placed):
        import jax
        import jax.numpy as jnp

        pts, vel, eps = placed
        n = pts.shape[0]
        fns = self._stage_fns()
        lab = jax.ShapeDtypeStruct((n,), jnp.int32)
        cnt = jax.ShapeDtypeStruct((self.capacity,), jnp.int32)
        args = {"fdbscan": (pts, eps), "halo_catalog": (pts, vel, lab),
                "halo_props": (pts, eps, lab, cnt)}
        for name, fn in fns.items():
            self.programs[name] = jax.jit(fn).lower(*args[name]).compile()

    # --- the timed step ----------------------------------------------------
    def run(self, placed, stages):
        pts, vel, eps = placed
        p = self.programs
        res = stages("fdbscan", p["fdbscan"], pts, eps)
        cat = stages("halo_catalog", p["halo_catalog"], pts, vel, res.labels)
        props = stages("halo_props", p["halo_props"], pts, eps,
                       cat.particle_halo, cat.count)
        return res, cat, props

    def counters(self, out):
        return {"union_rounds": out[0].num_rounds}

    def fetch(self, out):
        import jax

        res, cat, (mb, top, so) = jax.device_get(out)
        host = {"labels": res.labels, "num_rounds": int(res.num_rounds)}
        host.update({f: getattr(cat, f) for f in cat._fields})
        host.update({"mb_index": mb.index, "mb_center": mb.center,
                     "so_top": top, "so_r_delta": so.r_delta,
                     "so_m_delta": so.m_delta, "so_count": so.count,
                     "so_bracketed": so.bracketed})
        return host

    def release(self):
        self.programs.clear()

    # --- correctness ---------------------------------------------------------
    def reference(self, snap):
        return ref_lib.fof(snap.points, float(np.float32(snap.eps)))

    def check(self, snap, host, fof_ref):
        eps = float(np.float32(snap.eps))
        out = {"fof_mismatch": ref_lib.check_fof(host["labels"], fof_ref)}
        cat = ref_lib.catalog(snap.points, snap.velocities, host["labels"],
                              self.min_pts)
        out["catalog_mismatch"] = ref_lib.check_catalog_counts(host, cat)
        out["catalog_err"] = ref_lib.check_catalog_values(host, cat, eps)
        phi = ref_lib.potentials(snap.points, fof_ref, eps,
                                 float(np.float32(eps) * np.float32(1e-2)))
        out["center_phi_gap"] = ref_lib.check_centers(
            host["mb_index"], host["mb_center"], snap.points, cat, phi)
        out["so_violations"] = self._check_so(snap, host, cat)
        return out

    def _check_so(self, snap, host, cat):
        """The program's choice of the largest halos (by member count) and
        their SO answers."""
        so = self.so
        top = np.asarray(host["so_top"]).astype(np.int64)
        count = np.asarray(host["count"])
        if (top < 0).any() or (top >= len(count)).any():
            return len(top)
        got = count[top]
        valid = got > 0
        want = np.sort(cat.count)[::-1][:len(top)]
        bad = int(np.sum(np.sort(got)[::-1] != np.pad(
            want, (0, len(top) - len(want)))))
        centers = np.asarray(host["mb_center"])[top[valid]]
        return bad + ref_lib.check_so(
            centers, np.asarray(host["so_r_delta"])[valid],
            np.asarray(host["so_count"])[valid],
            np.asarray(host["so_m_delta"])[valid],
            np.asarray(host["so_bracketed"])[valid], snap.points,
            delta=float(so["delta"]), r_max=float(so["r_max"]),
            iters=int(so["iters"]))

    def control(self, snap):
        """The reference in the program's place, on bfloat16 particles."""
        pts, vel = _bf16(snap.points), _bf16(snap.velocities)
        eps = float(_bf16(np.float32(snap.eps)))
        fof = ref_lib.fof(pts, eps)
        cat = ref_lib.catalog(pts, vel, fof.labels, self.min_pts)
        h = len(cat.root)
        cap = self.capacity
        host = {"labels": fof.labels, "num_halos": h,
                "overflow": h > cap}

        def pad(x, fill=0):
            out = np.full((cap,) + x.shape[1:], fill, x.dtype)
            out[:min(h, cap)] = x[:cap]
            return out

        host.update(root=pad(cat.root, -1), count=pad(cat.count),
                    mass=pad(cat.count.astype(np.float64)),
                    center=pad(cat.center), vmean=pad(cat.vmean),
                    vdisp=pad(cat.vdisp), rmax=pad(cat.rmax),
                    particle_halo=cat.slot)
        phi = ref_lib.potentials(pts, fof, eps, eps * 1e-2)
        m = cat.slot >= 0
        best = np.full(h, np.inf)
        np.minimum.at(best, cat.slot[m], phi[m])
        hit = m & (phi <= best[np.clip(cat.slot, 0, None)])
        idx = np.full(h, len(pts), np.int64)
        np.minimum.at(idx, cat.slot[hit], np.flatnonzero(hit))
        host["mb_index"] = pad(idx, -1)
        host["mb_center"] = pad(np.asarray(snap.points)[idx])
        so = self.so
        k = int(so["halos"])
        top = np.argsort(-host["count"], kind="stable")[:k]
        r, cnt, bracketed = ref_lib.so_bisect(
            pts, host["mb_center"][top], delta=float(so["delta"]),
            r_max=float(so["r_max"]), iters=int(so["iters"]))
        host.update(so_top=top, so_r_delta=r, so_count=cnt,
                    so_m_delta=cnt.astype(np.float64),
                    so_bracketed=bracketed)
        return host

    # --- after the window, in a traced run ----------------------------------
    def probe(self, placed, out, snap, trace_call):
        """One library eps min-label pass under the profiler (its device
        time, for its roofline share), one eps within-pass with traversal
        counters, the tree build, and the compiled fdbscan's temporaries."""
        import jax
        import jax.numpy as jnp

        from repro.core.bvh import build_bvh
        from repro.core.dbscan import min_core_label_on
        from repro.core.geometry import scene_bounds
        from repro.core.query import query_count, within

        pts, _vel, eps = placed
        res = out[0]
        n = pts.shape[0]

        def build(points):
            lo, hi = scene_bounds(points)
            return build_bvh(points, lo, hi)

        def eps_pass(bvh, points, e, labels, core):
            return min_core_label_on(bvh, points, e, labels, core,
                                     jnp.ones((n,), bool), n)

        def lanes(bvh, points, e):
            _, st = query_count(bvh, within(points, e), with_stats=True)
            v = st.nodes_visited.astype(jnp.float32)
            return jnp.sum(v), jnp.max(v)

        build_c = jax.jit(build).lower(pts).compile()
        build_s, bvh = _timed_mean(build_c, pts)
        pass_c = jax.jit(eps_pass).lower(bvh, pts, eps, res.labels,
                                         res.core_mask).compile()
        pass_args = (bvh, pts, eps, res.labels, res.core_mask)
        jax.block_until_ready(pass_c(*pass_args))    # loaded and warm
        _, red = trace_call("eps_pass", pass_c, *pass_args)
        pass_s = red["busy_s"] if red and red["devices"] else None
        lane_sum, lane_max = jax.jit(lanes)(bvh, pts, eps)
        mem = self.programs_memory()
        return {"bvh_build_s": build_s, "eps_pass_device_s": pass_s,
                "lane_occupancy": float(lane_sum) / (n * float(lane_max)),
                "fdbscan_temp_bytes": mem.get("fdbscan_temp_bytes"),
                "n": n}

    def programs_memory(self):
        out = {}
        for name, c in self.programs.items():
            m = c.memory_analysis()
            if m is not None:
                out[f"{name}_temp_bytes"] = m.temp_size_in_bytes
        return out

    def probe_reference(self, snap):
        fof = self.reference(snap)
        return {"neighbor_total": ref_lib.neighbor_total(fof,
                                                         len(snap.points))}


def make(cfg, mix, chips, devices):
    return FofStep(cfg, mix, chips, devices)
