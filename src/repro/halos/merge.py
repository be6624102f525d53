"""Distributed halo-catalog reduction: merge per-shard partials by root.

``core/distributed.py`` ends with GLOBAL labels (cluster root = min global
particle id) sharded across the mesh. Halos straddle slab boundaries, so no
shard can finalize a catalog alone — the HACC pattern is: each rank reduces
its LOCAL particles into per-root partial sums, partial catalogs are merged
by root label across ranks, and centers-dependent quantities take one more
local pass.

The key identity (see ``catalog.py``): a partial-catalog row
``[count, Σx, Σv, Σ|v|²]`` is a weighted pseudo-particle in the exact
feature layout of the single-device reduction — so the cross-shard merge IS
``catalog.feature_sums``'s segmented reduction applied one level up, with
the partial rows as input and their stored counts as weights.

Protocol (``halo_catalog_sharded``, shard_map over the mesh axis):

1. every shard: ``partial_catalog`` over its local particles (one segmented
   reduction keyed on the global root label);
2. ``all_gather`` the fixed-capacity partial tables (S × H rows);
3. every shard runs the same deterministic ``merge_partial_catalogs`` →
   identical full catalogs, replicated;
4. max-radius second pass: each shard scatter-maxes its local particles'
   |x − center|² against the merged centers (root→slot via searchsorted on
   the catalog's ascending-root prefix), combined with ``lax.pmax``.

The pure functions (1)(3)(4) are also usable host-side without a mesh —
``tests/test_halos.py`` drives them shard-by-shard and checks exact
agreement with the single-device catalog.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.halos import catalog as _cat
from repro.halos.catalog import HaloCatalog, NOISE, _sort_last
from repro.kernels.segment import SEG_NEG_BIG

__all__ = [
    "PartialCatalog",
    "HaloPipelineResult",
    "partial_catalog",
    "merge_partial_catalogs",
    "local_rmax2",
    "particle_slots",
    "finalize_rmax",
    "halo_catalog_sharded",
    "halo_pipeline_sharded",
    "halo_pipeline_traced",
]


class PartialCatalog(NamedTuple):
    """Per-shard halo sums keyed by GLOBAL root label (-1 = empty row)."""

    root: jax.Array      # (H,) label dtype (int64 global ids at scale)
    sums: jax.Array      # (H, 2d+2) f32 — [count, Σx, Σv, Σ|v|²]
    overflow: jax.Array  # () bool


@partial(jax.jit, static_argnames=("capacity", "backend"))
def partial_catalog(points: jax.Array, velocities: jax.Array,
                    labels: jax.Array, *, capacity: int,
                    backend: str = "auto") -> PartialCatalog:
    """One shard's raw per-root sums (linear in particles — mergeable)."""
    sums, root, overflow, _, _, _ = _cat.feature_sums(
        points, velocities, labels, capacity=capacity, backend=backend)
    return PartialCatalog(root=root, sums=sums, overflow=overflow)


def merge_partial_catalogs(roots: jax.Array, sums: jax.Array, *,
                           capacity: int, min_count=2, particle_mass=1.0,
                           n_particles: int = 0) -> HaloCatalog:
    """Concatenated partial rows (S·H,) / (S·H, 2d+2) -> merged catalog.

    Rows are pseudo-particles: canonicalize roots, segment-sum the stored
    sums, derive. ``rmax`` needs particle data and comes back zeroed — run
    the ``local_rmax2`` + ``finalize_rmax`` second pass. ``particle_halo``
    is shape (n_particles,) of -1 (per-shard maps come from
    ``particle_slots``)."""
    d = (sums.shape[1] - 2) // 2
    # Empty partial rows (root -1 or zero count) become noise, then the rows
    # canonicalize exactly like particles do.
    roots_eff = jnp.where((roots >= 0) & (sums[:, 0] > 0), roots, -1)
    perm, pid_s, root_s, member_s, _nprov, overflow = \
        _cat.canonicalize_labels(roots_eff, capacity)

    rows = jnp.where(member_s[:, None], sums[perm], 0.0)
    # Merged rows count is small (S·H) — the plain scatter oracle is right.
    merged = jnp.zeros((capacity, sums.shape[1]), jnp.float32) \
        .at[pid_s].add(rows)
    sl = _sort_last(root_s.dtype)
    root_m = jnp.full((capacity,), sl, root_s.dtype) \
        .at[pid_s].min(jnp.where(member_s, root_s, sl))
    root_m = jnp.where(root_m == sl, NOISE, root_m).astype(root_s.dtype)

    (num_halos, root, count, mass, center, vmean, vdisp, _slot) = \
        _cat.derive_catalog(merged, root_m, min_count, particle_mass, d)
    return HaloCatalog(
        num_halos=num_halos, overflow=overflow, root=root, count=count,
        mass=mass, center=center, vmean=vmean, vdisp=vdisp,
        rmax=jnp.zeros((capacity,), jnp.float32),
        particle_halo=jnp.full((max(n_particles, 1),), -1, jnp.int32))


def particle_slots(labels: jax.Array, cat: HaloCatalog) -> jax.Array:
    """Root label per particle -> catalog slot (-1 if noise/cut), via
    searchsorted on the catalog's ascending-root valid prefix."""
    capacity = cat.root.shape[0]
    key = jnp.where(cat.count > 0, cat.root, _sort_last(cat.root.dtype))
    pos = jnp.searchsorted(key, jnp.maximum(labels, 0)).astype(jnp.int32)
    pos_c = jnp.clip(pos, 0, capacity - 1)
    found = (labels >= 0) & (pos < capacity) & (key[pos_c] == labels)
    return jnp.where(found, pos_c, -1)


def local_rmax2(points: jax.Array, labels: jax.Array,
                cat: HaloCatalog) -> jax.Array:
    """One shard's contribution to per-halo max |x − center|² (−BIG where
    the shard holds no members)."""
    capacity = cat.root.shape[0]
    slot = particle_slots(labels, cat)
    r2 = jnp.sum((points.astype(jnp.float32)
                  - cat.center[jnp.clip(slot, 0, capacity - 1)]) ** 2,
                 axis=-1)
    r2 = jnp.where(slot >= 0, r2, -SEG_NEG_BIG)
    return jnp.full((capacity,), -SEG_NEG_BIG, jnp.float32) \
        .at[jnp.clip(slot, 0, capacity - 1)].max(r2)


def finalize_rmax(cat: HaloCatalog, rmax2: jax.Array) -> HaloCatalog:
    """Install the (already cross-shard-combined) max radius²."""
    rmax = jnp.sqrt(jnp.maximum(rmax2, 0.0))
    return cat._replace(rmax=jnp.where(cat.count > 0, rmax, 0.0))


def halo_catalog_sharded(points: jax.Array, velocities: jax.Array,
                         labels: jax.Array, *, mesh: Mesh,
                         axis: str = "data", capacity: int,
                         min_count=2, particle_mass=1.0,
                         backend: str = "auto") -> HaloCatalog:
    """Sharded labels→catalog, composing with ``dbscan_distributed``.

    Inputs are (n_total, …) sharded along ``axis`` (same layout as
    ``dbscan_distributed``'s inputs/outputs; labels are its global root
    ids). Returns the catalog replicated, except ``particle_halo`` which is
    (n_total,) and sharded like the particles.
    """
    n_shards = mesh.shape[axis]
    local_cap = capacity

    def local_fn(pts, vel, lab):
        pts, vel, lab = pts[0], vel[0], lab[0]
        part = partial_catalog(pts, vel, lab, capacity=local_cap,
                               backend=backend)
        roots_all = jax.lax.all_gather(part.root, axis)        # (S, H)
        sums_all = jax.lax.all_gather(part.sums, axis)         # (S, H, F)
        cat = merge_partial_catalogs(
            roots_all.reshape(-1), sums_all.reshape(-1, sums_all.shape[-1]),
            capacity=capacity, min_count=min_count,
            particle_mass=particle_mass)
        rmax2 = jax.lax.pmax(local_rmax2(pts, lab, cat), axis)
        cat = finalize_rmax(cat, rmax2)
        ovf = jax.lax.psum(part.overflow.astype(jnp.int32), axis) > 0
        cat = cat._replace(overflow=cat.overflow | ovf)
        slots = particle_slots(lab, cat)
        return cat._replace(particle_halo=slots[None])

    rep = P()
    out_specs = HaloCatalog(
        num_halos=rep, overflow=rep, root=rep, count=rep, mass=rep,
        center=rep, vmean=rep, vdisp=rep, rmax=rep, particle_halo=P(axis))
    spec = P(axis, None)
    cat = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(spec, spec, P(axis, None)),
        out_specs=out_specs, check_vma=False,
    )(points.reshape(n_shards, -1, points.shape[-1]),
      velocities.reshape(n_shards, -1, velocities.shape[-1]),
      labels.reshape(n_shards, -1))
    return cat._replace(particle_halo=cat.particle_halo.reshape(-1))


class HaloPipelineResult(NamedTuple):
    """Everything the one-region pipeline produces in a single device launch."""
    labels: jax.Array         # (n_total,) global DBSCAN labels, sharded
    core_mask: jax.Array      # (n_total,) sharded
    rounds: jax.Array         # () int32 global merge rounds
    halo_overflow: jax.Array  # () bool — ghost buffer overflow anywhere
    catalog: HaloCatalog      # replicated (particle_halo sharded)
    so: "object"              # SoMassResult when so_delta was given, else None


def _pipeline_sharded_gated(fn):
    # jit gated on core count (see core.distributed._jit_ok: XLA:CPU's
    # busy-spin collective rendezvous deadlocks jitted shard_map programs
    # when simulated devices outnumber host cores).
    from repro.core.distributed import _maybe_jit

    return _maybe_jit(
        fn, static_argnames=("min_pts", "capacity", "halo_cap", "axis",
                             "mesh_ref", "min_count", "particle_mass",
                             "max_rounds", "backend", "so_delta", "box_volume",
                             "so_r_max", "so_iters", "index_dtype"))


@_pipeline_sharded_gated
def _pipeline_sharded(points, velocities, eps, min_pts, capacity, halo_cap,
                      axis, mesh_ref, min_count, particle_mass, max_rounds,
                      backend, so_delta, box_volume, so_r_max, so_iters,
                      index_dtype):
    from repro.core.distributed import dbscan_local_shard, shard_context
    from repro.halos.so_mass import so_masses_from_counts, sphere_counts

    mesh = mesh_ref.mesh
    n_shards = mesh.shape[axis]
    n_total = points.shape[0]

    def local_fn(pts, vel):
        pts, vel = pts[0], vel[0]
        # --- build + exchange + cluster (engine traversals, on device) ------
        ctx = shard_context(pts, eps, halo_cap, axis, n_shards,
                            index_dtype=index_dtype)
        labels, core, rounds = dbscan_local_shard(
            pts, eps, min_pts, ctx, axis=axis, max_rounds=max_rounds)
        # --- catalog: partial sums -> all_gather -> replicated merge --------
        part = partial_catalog(pts, vel, labels, capacity=capacity,
                               backend=backend)
        roots_all = jax.lax.all_gather(part.root, axis)
        sums_all = jax.lax.all_gather(part.sums, axis)
        cat = merge_partial_catalogs(
            roots_all.reshape(-1), sums_all.reshape(-1, sums_all.shape[-1]),
            capacity=capacity, min_count=min_count,
            particle_mass=particle_mass)
        rmax2 = jax.lax.pmax(local_rmax2(pts, labels, cat), axis)
        cat = finalize_rmax(cat, rmax2)
        ovf = jax.lax.psum(part.overflow.astype(jnp.int32), axis) > 0
        cat = cat._replace(overflow=cat.overflow | ovf)
        slots = particle_slots(labels, cat)
        cat = cat._replace(particle_halo=slots[None])
        outs = (labels[None], core[None], rounds[None],
                ctx.exchange.overflow[None], cat)
        if so_delta is not None:
            # SO masses against the LOCAL tree, psum'd across shards: the
            # centers are replicated, so every shard probes the same spheres
            # over its own particles and the sum is the global count.
            def count_fn(c, r):
                local = sphere_counts(ctx.bvh_local, pts, c, r)
                return jax.lax.psum(local, axis)

            so = so_masses_from_counts(
                count_fn, cat.center, cat.count > 0, delta=so_delta,
                particle_mass=particle_mass, n_particles=n_total,
                box_volume=box_volume, r_max=so_r_max, iters=so_iters)
            outs = outs + (so,)
        return outs

    rep = P()
    cat_spec = HaloCatalog(
        num_halos=rep, overflow=rep, root=rep, count=rep, mass=rep,
        center=rep, vmean=rep, vdisp=rep, rmax=rep, particle_halo=P(axis))
    out_specs = (P(axis), P(axis), P(axis), P(axis), cat_spec)
    if so_delta is not None:
        from repro.halos.so_mass import SoMassResult
        out_specs = out_specs + (SoMassResult(rep, rep, rep, rep),)
    spec = P(axis, None)
    res = jax.shard_map(
        local_fn, mesh=mesh, in_specs=(spec, spec), out_specs=out_specs,
        check_vma=False,
    )(points.reshape(n_shards, -1, points.shape[-1]),
      velocities.reshape(n_shards, -1, velocities.shape[-1]))
    labels, core, rounds, ovf, cat = res[:5]
    cat = cat._replace(particle_halo=cat.particle_halo.reshape(-1))
    return HaloPipelineResult(
        labels=labels.reshape(-1), core_mask=core.reshape(-1),
        rounds=jnp.max(rounds), halo_overflow=jnp.any(ovf), catalog=cat,
        so=res[5] if so_delta is not None else None)


def halo_pipeline_sharded(points: jax.Array, velocities: jax.Array, eps,
                          min_pts: int, *, mesh: Mesh, axis: str = "data",
                          capacity: int, halo_cap: int = 512,
                          min_count: int = 2, particle_mass: float = 1.0,
                          max_rounds: int = 64, backend: str = "auto",
                          so_delta: float | None = None,
                          box_volume: float = 1.0, so_r_max: float = 0.25,
                          so_iters: int = 20, index_dtype=jnp.int32,
                          tracer=None) -> HaloPipelineResult:
    """The paper's exascale pipeline in ONE ``shard_map`` region: per-shard
    BVH build → ε-ghost exchange → distributed DBSCAN → catalog merge →
    max-radius pass → (optionally, with ``so_delta``) SO masses — all engine
    traversals and collectives, zero host round-trips between stages.

    Inputs are (n_total, d) slab-partitioned like ``dbscan_distributed``'s
    (pre-sorted by x, n_total divisible by the axis size). The catalog is
    replicated; ``labels``/``core_mask``/``catalog.particle_halo`` are
    sharded like the particles.

    ``tracer`` (a ``repro.obs.SpanTracer``) wraps the launch in ONE fenced
    span — fusion means the host cannot see stage boundaries; for a
    per-stage trace use :func:`halo_pipeline_traced` (bit-identical staged
    composition, see ``tests/test_sharded_pipeline.py``)."""
    from repro.core.distributed import _mesh_ref

    def run():
        return _pipeline_sharded(
            points, velocities, eps, min_pts, int(capacity), halo_cap, axis,
            _mesh_ref(mesh), min_count, float(particle_mass), max_rounds,
            backend, so_delta, float(box_volume), float(so_r_max), so_iters,
            jnp.dtype(index_dtype))

    if tracer is None:
        return run()
    with tracer.span("halo_pipeline_sharded", n=int(points.shape[0]),
                     shards=int(mesh.shape[axis]), fused=True) as sp:
        res = sp.fence(run())
    tracer.counter("halo_pipeline", rounds=int(res.rounds),
                   num_halos=int(res.catalog.num_halos),
                   halo_overflow=int(res.halo_overflow))
    return res


def halo_pipeline_traced(points: jax.Array, velocities: jax.Array, eps,
                         min_pts: int, *, mesh: Mesh, axis: str = "data",
                         capacity: int, halo_cap: int = 512,
                         min_count: int = 2, particle_mass: float = 1.0,
                         max_rounds: int = 64, backend: str = "auto",
                         so_delta: float | None = None,
                         box_volume: float = 1.0, so_r_max: float = 0.25,
                         so_iters: int = 20, index_dtype=jnp.int32,
                         tracer=None) -> HaloPipelineResult:
    """The STAGED pipeline — ``dbscan_distributed`` → ``halo_catalog_sharded``
    → ``so_masses`` as separate launches, each in its own fenced span, so a
    Perfetto trace shows where the time goes. Produces the same result as
    the fused :func:`halo_pipeline_sharded` (the equivalence the sharded-
    pipeline tests assert), at the cost of host fences between stages —
    this is the observability build, not the production fast path."""
    from repro.core.distributed import dbscan_distributed
    from repro.halos.so_mass import so_masses
    from repro.obs.trace import traced

    def run():
        dd = dbscan_distributed(points, eps, min_pts, mesh=mesh, axis=axis,
                                halo_cap=halo_cap, max_rounds=max_rounds,
                                index_dtype=index_dtype, tracer=tracer)
        cat = traced(tracer, "halo_catalog_sharded", halo_catalog_sharded,
                     points, velocities, dd.labels, mesh=mesh, axis=axis,
                     capacity=int(capacity), min_count=min_count,
                     particle_mass=particle_mass, backend=backend)
        so = None
        if so_delta is not None:
            so = traced(tracer, "so_masses", so_masses, points, cat.center,
                        cat.count > 0, delta=so_delta,
                        particle_mass=particle_mass, box_volume=box_volume,
                        r_max=so_r_max, iters=so_iters)
        return HaloPipelineResult(
            labels=dd.labels, core_mask=dd.core_mask, rounds=dd.rounds,
            halo_overflow=dd.halo_overflow, catalog=cat, so=so)

    if tracer is None:
        return run()
    with tracer.span("halo_pipeline_traced", n=int(points.shape[0]),
                     shards=int(mesh.shape[axis]), fused=False):
        return run()
