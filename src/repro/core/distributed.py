"""Sharded geometric queries over a device mesh axis (paper §2/C9 — HACC's
MPI domain decomposition expressed in shard_map + collectives).

The file is layered so every sharded consumer (distributed DBSCAN, the halo
pipeline in ``repro.halos``, user query code) shares one substrate:

  1. ``slab_partition`` — host-side pre-partition: shard k owns the k-th
     contiguous slab along the first coordinate.
  2. ``halo_exchange`` — the ε-ghost exchange: each shard packs its boundary
     points (within ε of a slab face) into fixed-capacity buffers and ships
     them to the adjacent shards with ``ppermute`` (the MPI ghost-zone
     exchange). The routes are FIXED, so ``exchange_payload`` can later ship
     any per-point value (core flags, labels) along them without re-packing.
  3. ``shard_context`` — per-shard BVHs: one over local ∪ ghost points (cross-
     shard queries) and one over local points only (local union rounds, SO
     profiles). Invalid ghost rows are folded to a coordinate ≥ 4ε outside
     the local scene so they can never satisfy an ε-predicate AND never
     poison the Morton normalization (a BIG=1e15 fill would collapse every
     real point into one Morton bin — see ROADMAP item 3).
  4. ``sharded_query_csr`` / ``sharded_neighbor_csr`` — cross-shard queries
     through the device-resident CSR protocol (``query_csr_device``): per-
     shard build → exchange → traversal → scatter, all inside one
     ``shard_map`` region with zero host round-trips.
  5. ``dbscan_local_shard`` — the per-shard DBSCAN body (engine traversals,
     not dense O(n²) matrices), callable inside ANY shard_map region so
     larger pipelines (``repro.halos.merge.halo_pipeline_sharded``) can fuse
     clustering with catalog construction.
  6. ``dbscan_distributed`` — the standalone driver, same API as before.

Labels are GLOBAL point ids (shard * n_local + slot); cluster root = the
minimum global id in the cluster, noise = -1. Fixed shapes everywhere.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.bvh import Bvh, build_bvh
from repro.core.dbscan import count_neighbors, min_core_label_on, union_rounds
from repro.core.geometry import scene_bounds
from repro.core.query import (DeviceCsr, _canon_index_dtype,
                              query_csr_device, within)

__all__ = [
    "NOISE",
    "DistDbscanResult",
    "HaloExchange",
    "ShardContext",
    "ShardedCsr",
    "slab_partition",
    "halo_exchange",
    "exchange_payload",
    "shard_context",
    "sharded_query_csr",
    "sharded_neighbor_csr",
    "dbscan_local_shard",
    "dbscan_distributed",
]

NOISE = jnp.int32(-1)
BIG = 1e15


class DistDbscanResult(NamedTuple):
    labels: jax.Array      # (n_total,) global labels, sharded like points
    core_mask: jax.Array
    rounds: jax.Array      # () int32 global merge rounds
    halo_overflow: jax.Array  # () bool — halo capacity exceeded somewhere


class HaloExchange(NamedTuple):
    """Result of the ε-ghost exchange, with the fixed boundary routes kept so
    per-point payloads can be re-shipped later (``exchange_payload``)."""
    halo_pts: jax.Array    # (2H, d) ghost points; invalid rows folded ≥4ε out
    halo_valid: jax.Array  # (2H,) bool
    halo_gid: jax.Array    # (2H,) global ids (dtype follows gid), -1 invalid
    overflow: jax.Array    # () bool — any shard overflowed its halo buffer
    lidx: jax.Array        # (H,) local rows packed for the LEFT neighbor
    lvalid: jax.Array      # (H,) bool
    ridx: jax.Array        # (H,) local rows packed for the RIGHT neighbor
    rvalid: jax.Array      # (H,) bool
    n_shards: int          # python int — rebuilds the ppermute routes


class ShardContext(NamedTuple):
    """Per-shard sharded-query substrate (build once, query many). Global
    ids carry the caller's ``index_dtype`` — int64 (under x64) once
    ``n_shards * n_loc`` can exceed 2^31 (staticcheck rule W1)."""
    gid: jax.Array       # (n_loc,) index_dtype global ids of local points
    exchange: HaloExchange
    all_pts: jax.Array   # (n_loc + 2H, d) local ∪ ghost
    all_gid: jax.Array   # (n_loc + 2H,) index_dtype, -1 on invalid ghost rows
    bvh_all: Bvh         # tree over local ∪ ghost (cross-shard queries)
    bvh_local: Bvh       # tree over local points only
    sentinel: jax.Array  # () index_dtype = n_shards * n_loc (> any global id)


class ShardedCsr(NamedTuple):
    """Cross-shard CSR: per-shard rows over LOCAL queries, global object ids
    (offsets/indices/total carry the caller's ``index_dtype``)."""
    offsets: jax.Array     # (S, n_loc+1) per-shard row starts
    indices: jax.Array     # (S, capacity) GLOBAL point ids, -1 padded
    total: jax.Array       # (S,) hits per shard
    overflowed: jax.Array  # () bool — any shard exceeded ``capacity``


def slab_partition(points: np.ndarray, n_shards: int) -> tuple[np.ndarray, np.ndarray]:
    """Host-side pre-partition: sort by x and split into equal slabs (HACC
    ranks own spatial subvolumes). Returns (points_sorted, orig_index)."""
    order = np.argsort(points[:, 0], kind="stable")
    return points[order], order


def _pack_boundary(pts: jax.Array, mask: jax.Array, cap: int):
    """Pack masked rows into a fixed (cap, d) buffer (+global slot ids)."""
    n = pts.shape[0]
    order = jnp.argsort(~mask, stable=True)  # masked rows first
    idx = order[:cap]
    valid = mask[idx]
    buf = jnp.where(valid[:, None], pts[idx], BIG)
    count = jnp.sum(mask.astype(jnp.int32))
    return buf, idx, valid, count > cap


def _perms(n_shards: int):
    right_perm = [(i, i + 1) for i in range(n_shards - 1)]
    left_perm = [(i + 1, i) for i in range(n_shards - 1)]
    return right_perm, left_perm


def _xchg(axis: str, n_shards: int, val_r, val_l):
    """Send ``val_r`` to the right neighbor, ``val_l`` to the left. Devices
    with no sender (slab edges) receive ZEROS — all exchanged payloads are
    therefore decoded through a validity mask (or 0-means-absent encoding)."""
    right_perm, left_perm = _perms(n_shards)
    from_left = jax.lax.ppermute(val_r, axis, right_perm)
    from_right = jax.lax.ppermute(val_l, axis, left_perm)
    return from_left, from_right


def halo_exchange(pts: jax.Array, gid: jax.Array, eps, halo_cap: int,
                  axis: str, n_shards: int) -> HaloExchange:
    """The ε-ghost exchange (call inside a shard_map region): ship boundary
    points + their global ids to the adjacent shards along fixed routes.

    Invalid ghost rows (slab-edge fill, overflow padding) are folded to a
    point ≥ 4ε beyond the per-dim max of every real point this shard can see,
    so downstream ε-queries never match them and BVH quality is preserved."""
    eps = jnp.asarray(eps, pts.dtype)
    lo_x = jnp.min(pts[:, 0])
    hi_x = jnp.max(pts[:, 0])
    left_mask = pts[:, 0] <= lo_x + eps
    right_mask = pts[:, 0] >= hi_x - eps
    lbuf, lidx, lvalid, lovf = _pack_boundary(pts, left_mask, halo_cap)
    rbuf, ridx, rvalid, rovf = _pack_boundary(pts, right_mask, halo_cap)

    halo_l_pts, halo_r_pts = _xchg(axis, n_shards, rbuf, lbuf)
    # gid encoded +1 so the zero-fill at slab edges decodes to 'absent'.
    lgid_enc = jnp.where(lvalid, gid[lidx] + 1, 0)
    rgid_enc = jnp.where(rvalid, gid[ridx] + 1, 0)
    halo_l_enc, halo_r_enc = _xchg(axis, n_shards, rgid_enc, lgid_enc)
    halo_enc = jnp.concatenate([halo_l_enc, halo_r_enc])
    halo_valid = halo_enc > 0
    halo_gid = jnp.where(halo_valid, halo_enc - 1, -1).astype(gid.dtype)

    raw = jnp.concatenate([halo_l_pts, halo_r_pts])
    ghost_hi = jnp.max(jnp.where(halo_valid[:, None], raw,
                                 -jnp.inf).astype(pts.dtype), axis=0)
    ghost_lo = jnp.min(jnp.where(halo_valid[:, None], raw,
                                 jnp.inf).astype(pts.dtype), axis=0)
    hi_all = jnp.maximum(jnp.max(pts, axis=0), ghost_hi)
    lo_all = jnp.minimum(jnp.min(pts, axis=0), ghost_lo)
    span = jnp.max(hi_all - lo_all)
    fold = hi_all + 4.0 * eps + 1e-3 * span + 1e-6
    halo_pts = jnp.where(halo_valid[:, None], raw, fold)

    ovf = jax.lax.psum((lovf | rovf).astype(jnp.int32), axis) > 0
    return HaloExchange(halo_pts=halo_pts, halo_valid=halo_valid,
                        halo_gid=halo_gid, overflow=ovf,
                        lidx=lidx, lvalid=lvalid, ridx=ridx, rvalid=rvalid,
                        n_shards=n_shards)


def exchange_payload(ex: HaloExchange, values: jax.Array, fill,
                     axis: str) -> jax.Array:
    """Ship per-point ``values`` of the fixed boundary sets along the same
    routes the points took; rows with no sender (slab edges, overflow
    padding) decode to ``fill``. Returns (2H,) aligned with ``ex.halo_pts``."""
    fill = jnp.asarray(fill, values.dtype)
    lv = jnp.where(ex.lvalid, values[ex.lidx], fill)
    rv = jnp.where(ex.rvalid, values[ex.ridx], fill)
    hl, hr = _xchg(axis, ex.n_shards, rv, lv)
    out = jnp.concatenate([hl, hr])
    return jnp.where(ex.halo_valid, out, fill)


def shard_context(pts: jax.Array, eps, halo_cap: int, axis: str,
                  n_shards: int, *, use_64bit: bool = True,
                  index_dtype=jnp.int32) -> ShardContext:
    """Build the per-shard sharded-query substrate (call inside a shard_map
    region): ε-ghost exchange, then BVHs over local ∪ ghost and local-only
    points. Everything downstream — cross-shard CSR queries, distributed
    DBSCAN, catalog merge — runs off this context with no further host
    involvement. ``index_dtype`` sets the global-id dtype — int64 (under
    x64) once ``n_shards * n_loc`` can exceed 2^31."""
    idx_dt = _canon_index_dtype(index_dtype)
    n_loc = pts.shape[0]
    me = jax.lax.axis_index(axis).astype(idx_dt)
    gid = me * n_loc + jnp.arange(n_loc, dtype=idx_dt)
    ex = halo_exchange(pts, gid, eps, halo_cap, axis, n_shards)

    all_pts = jnp.concatenate([pts, ex.halo_pts])
    all_gid = jnp.concatenate([gid, ex.halo_gid])
    lo, hi = scene_bounds(all_pts)
    bvh_all = build_bvh(all_pts, lo, hi, use_64bit=use_64bit)
    lo_l, hi_l = scene_bounds(pts)
    bvh_local = build_bvh(pts, lo_l, hi_l, use_64bit=use_64bit)
    return ShardContext(gid=gid, exchange=ex, all_pts=all_pts,
                        all_gid=all_gid, bvh_all=bvh_all, bvh_local=bvh_local,
                        sentinel=jnp.asarray(n_shards * n_loc, idx_dt))


def sharded_query_csr(ctx: ShardContext, predicates, capacity: int, *,
                      axis: str, chunk: int = 32,
                      backend: str = "stackless") -> DeviceCsr:
    """Cross-shard device CSR (call inside a shard_map region): run the
    predicates against this shard's local ∪ ghost tree and remap hit indices
    to GLOBAL point ids (dtype follows ``ctx.gid``). No host sync — the
    result stays on device."""
    idx_dt = ctx.gid.dtype
    res = query_csr_device(ctx.bvh_all, predicates, capacity,
                           chunk=chunk, backend=backend, index_dtype=idx_dt)
    n_all = ctx.all_gid.shape[0]
    safe = jnp.clip(res.indices, 0, n_all - 1)
    gidx = jnp.where(res.indices >= 0, ctx.all_gid[safe], -1).astype(idx_dt)
    return DeviceCsr(offsets=res.offsets, indices=gidx, total=res.total,
                     overflowed=res.overflowed)


def _jit_ok() -> bool:
    """Whether shard_map drivers may run under one jitted SPMD program.

    XLA:CPU's collective rendezvous busy-spins: every simulated device in a
    jitted shard_map program needs a core of its own, or a rank still inside
    a long traversal while_loop is starved by a peer spinning at a
    ``ppermute`` and the program deadlocks (the "waiting for all participants
    to arrive at rendezvous" hang). When the host has fewer cores than local
    devices, fall back to eager shard_map — per-primitive dispatch completes
    each collective before the next op is launched and never spins.
    Override with ``REPRO_SHARDED_JIT=0|1``.
    """
    env = os.environ.get("REPRO_SHARDED_JIT")
    if env is not None:
        return env not in ("0", "false", "False")
    if jax.default_backend() != "cpu":
        return True
    return (os.cpu_count() or 1) >= jax.local_device_count()


def _maybe_jit(fn, *, static_argnames):
    """``jax.jit`` for shard_map drivers, gated per call by ``_jit_ok``."""
    jitted = jax.jit(fn, static_argnames=static_argnames)

    @functools.wraps(fn)
    def run(*args, **kwargs):
        return (jitted if _jit_ok() else fn)(*args, **kwargs)

    return run


def _mesh_ref(mesh: Mesh):
    class _Ref:
        def __init__(self, m):
            self.mesh = m

        def __hash__(self):
            return hash(id(self.mesh))

        def __eq__(self, other):
            return self.mesh is getattr(other, "mesh", None)

    return _Ref(mesh)


@functools.partial(_maybe_jit,
                   static_argnames=("capacity", "halo_cap", "axis", "mesh_ref",
                                    "chunk", "backend", "use_64bit",
                                    "index_dtype"))
def _neighbor_csr_sharded(points, eps, capacity, halo_cap, axis, mesh_ref,
                          chunk, backend, use_64bit, index_dtype):
    mesh = mesh_ref.mesh
    n_shards = mesh.shape[axis]

    def local_fn(pts):
        pts = pts[0]
        ctx = shard_context(pts, eps, halo_cap, axis, n_shards,
                            use_64bit=use_64bit, index_dtype=index_dtype)
        pred = within(pts, jnp.asarray(eps, pts.dtype))
        res = sharded_query_csr(ctx, pred, capacity, axis=axis,
                                chunk=chunk, backend=backend)
        ovf = jax.lax.psum(res.overflowed.astype(jnp.int32), axis) > 0
        halo_ovf = ctx.exchange.overflow
        return (res.offsets[None], res.indices[None], res.total[None],
                (ovf | halo_ovf)[None])

    spec_in = P(axis, None)
    offsets, indices, total, ovf = jax.shard_map(
        local_fn, mesh=mesh, in_specs=(spec_in,),
        out_specs=(P(axis, None), P(axis, None), P(axis), P(axis)),
        check_vma=False,
    )(points.reshape(n_shards, -1, points.shape[-1]))
    return offsets, indices, total, jnp.any(ovf)


def sharded_neighbor_csr(points: jax.Array, eps, *, capacity: int, mesh: Mesh,
                         axis: str = "data", halo_cap: int = 512,
                         chunk: int = 32, backend: str = "stackless",
                         use_64bit: bool = True, index_dtype=jnp.int32,
                         tracer=None) -> ShardedCsr:
    """The reusable sharded-query layer, end to end: slab-sharded points in,
    per-shard ε-neighbor CSR out (GLOBAL point ids, self included), computed
    as per-shard BVH build → ppermute ghost exchange → device-resident CSR —
    one shard_map region, zero host round-trips.

    ``points``: (n_total, d) pre-sorted by x (``slab_partition``), n_total
    divisible by the axis size. ``capacity`` bounds hits PER SHARD.
    ``index_dtype``: global-id/offset dtype — int64 (under x64) once
    ``n_total`` or per-shard hits can exceed 2^31.

    ``tracer`` (a ``repro.obs.SpanTracer``) wraps the fused launch in one
    fenced span — the exchange/build/query phases share a single shard_map
    region by design, so the host sees them as one launch — and samples the
    per-shard hit totals onto a counter track after the fence."""
    idx_dt = _canon_index_dtype(index_dtype)
    if tracer is None:
        offsets, indices, total, ovf = _neighbor_csr_sharded(
            points, eps, int(capacity), halo_cap, axis, _mesh_ref(mesh),
            chunk, backend, use_64bit, idx_dt)
        return ShardedCsr(offsets=offsets, indices=indices, total=total,
                          overflowed=ovf)
    with tracer.span("sharded_neighbor_csr", n=int(points.shape[0]),
                     shards=int(mesh.shape[axis]), backend=backend) as sp:
        offsets, indices, total, ovf = sp.fence(_neighbor_csr_sharded(
            points, eps, int(capacity), halo_cap, axis, _mesh_ref(mesh),
            chunk, backend, use_64bit, idx_dt))
    tracer.counter("csr_hits", total=int(jnp.sum(total)),
                   overflowed=int(ovf))
    return ShardedCsr(offsets=offsets, indices=indices, total=total,
                      overflowed=ovf)


def dbscan_local_shard(pts: jax.Array, eps, min_pts: int, ctx: ShardContext,
                       *, axis: str, max_rounds: int = 64):
    """Per-shard DBSCAN body (call inside a shard_map region): engine
    traversals over the shard-context trees replace the dense O(n²) neighbor
    matrices the original implementation staged.

      - core test: ε-counts over local ∪ ghost with early exit at min_pts
      - local components: ``union_rounds`` fixpoint on the local tree
      - global merge: exchange boundary labels, min-core-label traversal,
        hook onto local roots, repeat until a ``psum`` fixpoint
      - border points: final min-core-label pass over local ∪ ghost

    Returns (labels, core_mask, rounds) for the local points; labels are
    global point ids, noise = -1."""
    n_loc = pts.shape[0]
    eps_f = jnp.asarray(eps, pts.dtype)
    ex = ctx.exchange
    sentinel = ctx.sentinel

    # --- core classification: ε-counts over local ∪ ghost ------------------
    counts = count_neighbors(ctx.bvh_all, ctx.all_pts, pts, eps_f,
                             min_pts=min_pts)
    core = counts >= min_pts
    halo_core = exchange_payload(ex, core.astype(jnp.int32), 0, axis) > 0
    all_core = jnp.concatenate([core, halo_core])

    # --- local components: union fixpoint on the local tree -----------------
    local_root, _ = union_rounds(ctx.bvh_local, pts, eps_f, core, n_loc,
                                 max_rounds=max_rounds)
    idx_dt = ctx.gid.dtype
    labels0 = jnp.where(core, ctx.gid[local_root], sentinel).astype(idx_dt)

    def halo_labels(labels):
        return exchange_payload(ex, labels, sentinel, axis)

    def cond(state):
        _, changed, r = state
        return changed & (r < max_rounds)

    def body(state):
        labels, _, r = state
        all_labels = jnp.concatenate([labels, halo_labels(labels)])
        m = min_core_label_on(ctx.bvh_all, pts, eps_f, all_labels, all_core,
                              core, sentinel)
        m = jnp.where(core, jnp.minimum(labels, m), sentinel)
        # scatter the min onto the LOCAL root, then broadcast back
        root_min = jnp.full((n_loc,), sentinel, idx_dt) \
            .at[local_root].min(m)
        new = jnp.where(core, root_min[local_root], labels).astype(idx_dt)
        changed_local = jnp.any(new != labels)
        changed = jax.lax.psum(changed_local.astype(jnp.int32), axis) > 0
        return new, changed, r + 1

    # psum-derived init: INVARIANT vma, matching the body's psum output
    changed0 = jax.lax.psum(jnp.int32(1), axis) > 0
    labels, _, rounds = jax.lax.while_loop(
        cond, body, (labels0, changed0, jnp.int32(0)))

    # --- border points -------------------------------------------------------
    all_labels = jnp.concatenate([labels, halo_labels(labels)])
    border = min_core_label_on(ctx.bvh_all, pts, eps_f, all_labels, all_core,
                               ~core, sentinel)
    final = jnp.where(core, labels,
                      jnp.where(border < sentinel, border, NOISE))
    final = jnp.where(final == sentinel, NOISE, final)
    return final.astype(idx_dt), core, rounds


@functools.partial(_maybe_jit,
                   static_argnames=("min_pts", "halo_cap", "axis", "mesh_ref",
                                    "max_rounds", "index_dtype"))
def _dbscan_sharded(points, eps, min_pts, halo_cap, axis, mesh_ref, max_rounds,
                    index_dtype):
    mesh = mesh_ref.mesh
    n_shards = mesh.shape[axis]

    def local_fn(pts):
        pts = pts[0]                                  # drop leading shard dim
        ctx = shard_context(pts, eps, halo_cap, axis, n_shards,
                            index_dtype=index_dtype)
        labels, core, rounds = dbscan_local_shard(
            pts, eps, min_pts, ctx, axis=axis, max_rounds=max_rounds)
        return (labels[None], core[None], rounds[None],
                ctx.exchange.overflow[None])

    spec_in = P(axis, None)
    # check_vma=False: the union fixpoints' while_loop carries mix per-shard
    # values with psum'd (shard-invariant) ones.
    labels, core, rounds, ovf = jax.shard_map(
        local_fn, mesh=mesh, in_specs=(spec_in,),
        out_specs=(P(axis), P(axis), P(axis), P(axis)),
        check_vma=False,
    )(points.reshape(n_shards, -1, points.shape[-1]))
    return (labels.reshape(-1), core.reshape(-1), jnp.max(rounds),
            jnp.any(ovf))


def dbscan_distributed(points: jax.Array, eps, min_pts: int, *, mesh: Mesh,
                       axis: str = "data", halo_cap: int = 512,
                       max_rounds: int = 64, index_dtype=jnp.int32,
                       tracer=None) -> DistDbscanResult:
    """points: (n_total, d), n_total divisible by the axis size, pre-sorted
    by x (``slab_partition``) so shard slabs are contiguous. ``index_dtype``
    sets the global-label dtype — int64 (under x64) once ``n_total`` can
    exceed 2^31.

    ``tracer`` (a ``repro.obs.SpanTracer``) wraps the fused
    exchange + core-test + union-fixpoint launch in one fenced span and
    records the merge round count / halo overflow after the fence."""
    idx_dt = _canon_index_dtype(index_dtype)
    if tracer is None:
        labels, core, rounds, ovf = _dbscan_sharded(
            points, eps, min_pts, halo_cap, axis, _mesh_ref(mesh), max_rounds,
            idx_dt)
        return DistDbscanResult(labels=labels, core_mask=core, rounds=rounds,
                                halo_overflow=ovf)
    with tracer.span("dbscan_distributed", n=int(points.shape[0]),
                     shards=int(mesh.shape[axis]), min_pts=int(min_pts)) as sp:
        labels, core, rounds, ovf = sp.fence(_dbscan_sharded(
            points, eps, min_pts, halo_cap, axis, _mesh_ref(mesh), max_rounds,
            idx_dt))
    tracer.counter("dbscan_rounds", rounds=int(rounds),
                   halo_overflow=int(ovf))
    return DistDbscanResult(labels=labels, core_mask=core, rounds=rounds,
                            halo_overflow=ovf)
