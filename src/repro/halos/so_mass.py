"""Spherical-overdensity (SO) halo masses via BVH range counts.

The production quantity downstream of FOF/DBSCAN halo finding (HACC's SO
stage, Rockstar's M200): around each halo center, find the radius R_Δ where
the mean enclosed density crosses Δ × the reference density, and report

    M_Δ = (particles inside R_Δ) × particle_mass.

Enclosed counts are ε-sphere range counts on the SAME BVH the clustering
uses — ``sphere_counts`` is the query engine's count protocol with a
PER-QUERY radius (``within(centers, radii)``: each halo probes its own
candidate R via the predicate's radius lane). R_Δ is located by
fixed-iteration bisection (jit-able, fixed shapes): enclosed mean density
is monotonically decreasing outside the core, so ``iters`` halvings
bracket R_Δ to ``r_hi / 2^iters``.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.bvh import Bvh, build_bvh
from repro.core.geometry import scene_bounds
from repro.core.query import query_count, within

__all__ = ["SoMassResult", "sphere_counts", "so_masses",
           "so_masses_from_counts"]

_FOUR_THIRDS_PI = 4.0 / 3.0 * jnp.pi


class SoMassResult(NamedTuple):
    r_delta: jax.Array   # (H,) f32 — SO radius (0 at invalid slots)
    m_delta: jax.Array   # (H,) f32 — count(R_Δ) * particle_mass
    count: jax.Array     # (H,) int32 — particles inside R_Δ
    bracketed: jax.Array  # (H,) bool — density fell below Δρ_ref by r_max;
    #   False means R_Δ >= r_max and r_delta/m_delta are clamped
    #   underestimates (raise r_max), not converged values.


def sphere_counts(bvh, points: jax.Array, centers: jax.Array,
                  radii: jax.Array) -> jax.Array:
    """Range counts with a per-query radius vector (radii: scalar or (q,)).

    One engine call: ``within`` predicates carry the per-halo radii, the
    count protocol does the rest. ``points`` is kept in the signature for
    backward compatibility (the engine tests leaf volumes directly)."""
    return query_count(
        bvh, within(centers.astype(jnp.float32),
                    jnp.asarray(radii, jnp.float32)))


def so_masses_from_counts(count_fn, centers: jax.Array, valid: jax.Array, *,
                          delta, particle_mass, n_particles, box_volume,
                          r_max, iters: int) -> SoMassResult:
    """The bisection driver, decoupled from WHERE counts come from.

    ``count_fn(centers, radii) -> (H,) int`` returns enclosed particle
    counts; the single-device path closes over a local BVH, the sharded
    pipeline closes over the per-shard tree and ``psum``s across shards —
    either way the driver is one fixed-iteration device loop, so it can run
    inside a ``shard_map`` region with zero host round-trips.
    ``n_particles`` is the GLOBAL particle count defining the reference
    density ``n × particle_mass / box_volume``."""
    with jax.named_scope("halos.so_bisect"):
        rho_ref = (jnp.asarray(delta, jnp.float32)
                   * n_particles * jnp.asarray(particle_mass, jnp.float32)
                   / jnp.asarray(box_volume, jnp.float32))
        m = jnp.asarray(particle_mass, jnp.float32)
        valid_f = valid.astype(jnp.float32)

        def body(_, state):
            r_lo, r_hi = state
            mid = 0.5 * (r_lo + r_hi)
            cnt = count_fn(centers, mid * valid_f)
            dens = cnt.astype(jnp.float32) * m \
                / (_FOUR_THIRDS_PI * jnp.maximum(mid, 1e-12) ** 3)
            above = dens >= rho_ref
            return jnp.where(above, mid, r_lo), jnp.where(above, r_hi, mid)

        r0 = jnp.full((centers.shape[0],), jnp.asarray(r_max, jnp.float32))
        r_lo, r_hi = jax.lax.fori_loop(0, iters, body,
                                       (jnp.zeros_like(r0), r0))
        r_delta = jnp.where(valid, r_lo, 0.0)
        count = count_fn(centers, r_delta * valid_f)
        count = jnp.where(valid, count, 0)
        # Bracket check: did the density cross Δρ_ref inside [0, r_max]?
        cnt_edge = count_fn(centers, r0 * valid_f)
        dens_edge = (cnt_edge.astype(jnp.float32) * m
                     / (_FOUR_THIRDS_PI * r0 ** 3))
        return SoMassResult(r_delta=r_delta,
                            m_delta=count.astype(jnp.float32) * m,
                            count=count,
                            bracketed=valid & (dens_edge < rho_ref))


@partial(jax.jit, static_argnames=("iters", "use_64bit"))
def so_masses(points: jax.Array, centers: jax.Array, valid: jax.Array, *,
              delta=200.0, particle_mass=1.0, box_volume=1.0,
              r_max=0.25, iters: int = 20, bvh: Bvh | None = None,
              use_64bit: bool = True) -> SoMassResult:
    """M_Δ / R_Δ around ``centers`` (e.g. the catalog's centers or the
    most-bound proxies). ``valid`` masks real halo slots; invalid slots are
    probed at radius 0 and return zeros. ``bvh``: optional prebuilt tree
    over ``points`` (skips the rebuild when chained after other stages).

    The reference density is the mean particle density
    ``n × particle_mass / box_volume`` (matter-density convention — the
    usual Δ=200 "M200m"-style mass for a unit-box mock).
    """
    n = points.shape[0]
    if bvh is None:
        lo_box, hi_box = scene_bounds(points)
        bvh = build_bvh(points, lo_box, hi_box, use_64bit=use_64bit)
    tree = bvh

    def count_fn(c, r):
        return sphere_counts(tree, points, c, r)

    return so_masses_from_counts(count_fn, centers, valid, delta=delta,
                                 particle_mass=particle_mass, n_particles=n,
                                 box_volume=box_volume, r_max=r_max,
                                 iters=iters)
