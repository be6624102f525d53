"""Halo catalogs from DBSCAN labels: fixed-capacity, jit-able reductions.

The paper's challenge problem (§2) ends where our DBSCAN ladder stops — raw
int32 labels. HACC's actual in-situ deliverable is a halo CATALOG: per-halo
particle counts, masses, centers of mass, mean velocities, velocity
dispersions and radii, computed on-device every analysis step (these feed
merger trees and downstream science). This module is that missing half.

Pipeline (all fixed shapes, one jit):

1. **Canonicalization** (``canonicalize_labels``): sort particles by cluster
   root label (noise sorts last under a +inf key); run heads mark new halos;
   ``cumsum(head) - 1`` assigns DENSE provisional halo ids ``0..nprov-1`` in
   ascending-root order. Ids beyond ``capacity`` are dropped and flagged.
2. **Segmented reductions**: an 8-wide feature row per sorted particle —
   ``[1, x, y, z, vx, vy, vz, |v|²]`` — is segment-summed by halo id, giving
   count, Σx, Σv, Σ|v|² in one pass. Because ids are sorted AND dense this
   runs on the Pallas one-hot-matmul kernel (``kernels/segment.py``) or the
   pure-JAX scatter oracle, selected by ``backend``.
3. **Derived quantities**: center of mass, mean velocity, 3-D velocity
   dispersion σ = sqrt(E|v|² − |Ev|²); a second segmented MAX pass over
   |x − center|² yields the max radius.
4. **Mass cut + compaction**: halos with fewer than ``min_count`` particles
   (HACC cuts tiny halos; pass your DBSCAN ``min_pts`` for the paper's cut)
   are dropped and survivors compacted to slots ``0..num_halos-1``, still in
   ascending-root order — so ``catalog.root``'s valid prefix is sorted, and
   root→slot lookup is a ``searchsorted`` (``merge.py`` relies on this).

The same feature-row layout is reused by ``merge.py``: a per-shard partial
catalog row ``[count, Σx, Σv, Σ|v|²]`` is just a weighted pseudo-particle,
so the cross-shard merge is this module's reduction applied one level up.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ref as _kref
from repro.kernels import segment as _kseg

NOISE = jnp.int32(-1)


def _sort_last(dtype):
    """The sort-to-the-back sentinel for a label dtype: iinfo max, so int64
    global labels (distributed runs past 2^31 points) keep a sentinel above
    every real root instead of colliding with hard-coded 2^31-1."""
    return jnp.asarray(jnp.iinfo(jnp.dtype(dtype)).max, dtype)


_SORT_LAST = _sort_last(jnp.int32)  # legacy alias for int32-label callers

__all__ = [
    "NOISE",
    "HaloCatalog",
    "canonicalize_labels",
    "feature_sums",
    "derive_catalog",
    "halo_catalog",
]


class HaloCatalog(NamedTuple):
    """Fixed-capacity halo catalog. Valid halos occupy slots
    ``0..num_halos-1`` (ascending DBSCAN root label); the rest are zeroed
    with ``root == -1``."""

    num_halos: jax.Array      # () int32 — halos surviving the mass cut
    overflow: jax.Array       # () bool — provisional halos exceeded capacity
    root: jax.Array           # (H,) label dtype — DBSCAN root label, -1 empty
    count: jax.Array          # (H,) int32 — particles in halo
    mass: jax.Array           # (H,) f32 — count * particle_mass
    center: jax.Array         # (H, d) f32 — center of mass
    vmean: jax.Array          # (H, d) f32 — mean velocity
    vdisp: jax.Array          # (H,) f32 — 3-D velocity dispersion σ
    rmax: jax.Array           # (H,) f32 — max |x - center| over members
    particle_halo: jax.Array  # (n,) int32 — final slot per particle, -1 none


def _use_pallas(backend: str) -> bool:
    if backend not in ("auto", "pallas", "jax"):
        raise ValueError(f"unknown backend {backend!r}")
    return backend == "pallas" or (backend == "auto"
                                   and jax.default_backend() == "tpu")


def _seg_sum(data, seg, num_segments, backend):
    if _use_pallas(backend):
        return _kseg.segment_sum_sorted(data, seg, num_segments)
    return _kref.segment_sum_sorted_ref(data, seg, num_segments)


def _seg_max(data, seg, num_segments, backend):
    if _use_pallas(backend):
        return _kseg.segment_max_sorted(data, seg, num_segments)
    return _kref.segment_max_sorted_ref(data, seg, num_segments)


def canonicalize_labels(labels: jax.Array, capacity: int):
    """Labels -> dense provisional halo ids via sort/segment ops.

    Returns ``(perm, pid_sorted, labels_sorted, member_sorted, nprov,
    overflow)``: ``perm`` sorts particles by root label (noise last);
    ``pid_sorted`` is the dense id per sorted particle, clipped into
    ``[0, capacity)`` and constant over the noise tail (its rows are masked
    out by ``member_sorted``, which is False for noise AND for particles of
    halos beyond capacity)."""
    n = labels.shape[0]
    valid = labels >= 0
    sl = _sort_last(labels.dtype)
    perm = jnp.argsort(jnp.where(valid, labels, sl),
                       stable=True).astype(jnp.int32)
    lab_s = labels[perm]  # keeps the label dtype (int64 global ids at scale)
    valid_s = valid[perm]
    idx = jnp.arange(n, dtype=jnp.int32)
    head = valid_s & ((idx == 0) | (lab_s != jnp.roll(lab_s, 1)))
    pid_raw = jnp.cumsum(head.astype(jnp.int32)) - 1
    nprov = pid_raw[-1] + 1 if n else jnp.int32(0)
    overflow = nprov > capacity
    member_s = valid_s & (pid_raw < capacity)
    pid_s = jnp.clip(pid_raw, 0, capacity - 1)
    return perm, pid_s, lab_s, member_s, nprov, overflow


def feature_sums(points, velocities, labels, *, capacity: int,
                 backend: str = "auto"):
    """Per-provisional-halo raw sums ``[count, Σx, Σv, Σ|v|²]`` (H, 2d+2),
    plus the root label per halo and the canonicalization artifacts.

    This is the per-shard "partial catalog" primitive: the sums combine
    linearly across shards (see ``merge.py``)."""
    perm, pid_s, lab_s, member_s, nprov, overflow = \
        canonicalize_labels(labels, capacity)
    pts_s = points[perm].astype(jnp.float32)
    vel_s = velocities[perm].astype(jnp.float32)
    w = member_s.astype(jnp.float32)[:, None]
    feats = jnp.concatenate(
        [w, pts_s * w, vel_s * w,
         jnp.sum(vel_s ** 2, axis=-1, keepdims=True) * w], axis=1)
    sums = _seg_sum(feats, pid_s, capacity, backend)
    sl = _sort_last(lab_s.dtype)
    root = jnp.full((capacity,), sl, lab_s.dtype) \
        .at[pid_s].min(jnp.where(member_s, lab_s, sl))
    root = jnp.where(root == sl, NOISE, root).astype(lab_s.dtype)
    return sums, root, overflow, perm, pid_s, member_s


def derive_catalog(sums, root, min_count, particle_mass, d: int):
    """Raw sums -> derived per-halo quantities + mass cut + compaction.

    Returns ``(num_halos, root, count, mass, center, vmean, vdisp,
    slot_of_prov)`` where ``slot_of_prov[p]`` maps a provisional halo to its
    final slot (-1 if cut). Compaction is stable, so surviving roots stay in
    ascending order."""
    capacity = sums.shape[0]
    cnt_f = sums[:, 0]
    count = jnp.round(cnt_f).astype(jnp.int32)
    safe = jnp.maximum(cnt_f, 1.0)
    center = sums[:, 1:1 + d] / safe[:, None]
    vmean = sums[:, 1 + d:1 + 2 * d] / safe[:, None]
    ev2 = sums[:, 1 + 2 * d] / safe
    vdisp = jnp.sqrt(jnp.maximum(ev2 - jnp.sum(vmean ** 2, axis=-1), 0.0))

    keep = count >= jnp.maximum(jnp.asarray(min_count, jnp.int32), 1)
    order = jnp.argsort(~keep, stable=True).astype(jnp.int32)
    kept = keep[order]
    num_halos = jnp.sum(keep.astype(jnp.int32))
    slot_of_prov = jnp.zeros((capacity,), jnp.int32) \
        .at[order].set(jnp.arange(capacity, dtype=jnp.int32))
    slot_of_prov = jnp.where(keep, slot_of_prov, -1)

    def compact(a, fill):
        out = a[order]
        mask = kept if a.ndim == 1 else kept[:, None]
        return jnp.where(mask, out, jnp.asarray(fill, a.dtype))

    return (num_halos,
            compact(root, NOISE),
            compact(count, 0),
            compact(cnt_f * jnp.asarray(particle_mass, jnp.float32), 0.0),
            compact(center, 0.0),
            compact(vmean, 0.0),
            compact(vdisp, 0.0),
            slot_of_prov)


@partial(jax.jit, static_argnames=("capacity", "backend"))
def halo_catalog(points: jax.Array, velocities: jax.Array, labels: jax.Array,
                 *, capacity: int, min_count=2, particle_mass=1.0,
                 backend: str = "auto") -> HaloCatalog:
    """DBSCAN labels + phase-space coordinates -> halo catalog.

    ``labels``: (n,) int32 cluster roots (any DBSCAN variant's output, or
    the global ids of ``core/distributed.py``), noise = -1.
    ``capacity``: static max halos; more sets ``overflow`` and drops the
    largest-root surplus. ``min_count``: minimum members (pass the DBSCAN
    ``min_pts`` for the paper's mass cut). ``backend``: "pallas" | "jax" |
    "auto" (Pallas on TPU, scatter oracle elsewhere).
    """
    with jax.named_scope("halos.catalog"):
        n, d = points.shape
        sums, root_p, overflow, perm, pid_s, member_s = feature_sums(
            points, velocities, labels, capacity=capacity, backend=backend)
        (num_halos, root, count, mass, center, vmean, vdisp,
         slot_of_prov) = derive_catalog(sums, root_p, min_count,
                                        particle_mass, d)

        # Second pass: max radius about the (provisional) center of mass.
        cnt_f = sums[:, 0]
        center_p = sums[:, 1:1 + d] / jnp.maximum(cnt_f, 1.0)[:, None]
        r2_s = jnp.sum(
            (points[perm].astype(jnp.float32) - center_p[pid_s]) ** 2,
            axis=-1)
        r2_s = jnp.where(member_s, r2_s, -_kseg.SEG_NEG_BIG)
        rmax2_p = _seg_max(r2_s[:, None], pid_s, capacity, backend)[:, 0]
        rmax_p = jnp.sqrt(jnp.maximum(rmax2_p, 0.0))
        # Route each surviving provisional halo's rmax to its compacted slot
        # (cut halos collapse onto slot 0 with a harmless 0-valued max update).
        rmax = jnp.zeros((capacity,), jnp.float32) \
            .at[jnp.clip(slot_of_prov, 0, capacity - 1)] \
            .max(jnp.where(slot_of_prov >= 0, rmax_p, 0.0))

        halo_s = jnp.where(member_s, slot_of_prov[pid_s], -1)
        particle_halo = jnp.zeros((n,), jnp.int32).at[perm].set(halo_s)

        return HaloCatalog(num_halos=num_halos, overflow=overflow, root=root,
                           count=count, mass=mass, center=center, vmean=vmean,
                           vdisp=vdisp, rmax=rmax, particle_halo=particle_halo)
