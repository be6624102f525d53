"""Most-bound-particle proxy halo centers via BVH ε-neighborhood potentials.

Center-of-mass centers (``catalog.py``) are biased by tidal debris and
infalling substructure; halo finders (Rockstar, HACC's SO stage) prefer the
MOST BOUND PARTICLE — the minimum of the gravitational potential — as the
halo center. The full O(n²) potential is out of budget in-situ, so we use
the standard short-range proxy: a softened potential truncated at ε,

    φ_i = − Σ_{j : r_ij ≤ ε}  1 / sqrt(r_ij² + soft²),

evaluated with the SAME fused query engine the DBSCAN ladder uses
(``core/query.py``: a ``within`` predicate + accumulating callback,
§4.1.1, which receives the squared pair distance from the predicate
gate) — each particle's potential is one ε-query, no neighbor lists
materialized. The self term 1/soft is a constant shift and cannot change
the per-halo argmin.

The per-halo argmin is two segmented scatter-mins over the catalog's
particle→slot map: min potential, then min particle index attaining it
(deterministic tie-break by original index).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.bvh import Bvh, build_bvh
from repro.core.geometry import scene_bounds
from repro.core.query import query, within

_BIG = jnp.float32(1e30)

__all__ = ["MostBoundResult", "halo_potentials", "most_bound_centers"]


class MostBoundResult(NamedTuple):
    index: jax.Array      # (H,) int32 — most-bound particle id, -1 empty slot
    center: jax.Array     # (H, d) f32 — its position (0 at empty slots)
    potential: jax.Array  # (H,) f32 — its φ (0 at empty slots)


def halo_potentials(points: jax.Array, eps, *, softening=None,
                    active: jax.Array | None = None,
                    bvh: Bvh | None = None,
                    use_64bit: bool = True) -> jax.Array:
    """Softened ε-truncated potential per particle (lower = more bound).

    ``active`` masks queries: inactive particles (noise) return 0 — note
    they still walk the tree (the mask gates the output, not the traversal),
    so cost scales with n, not member count. Pass ``bvh`` to reuse a tree
    built over the SAME ``points`` (e.g. across pipeline stages)."""
    eps_f = jnp.asarray(eps, jnp.float32)
    soft2 = jnp.square(eps_f * 1e-2 if softening is None
                       else jnp.asarray(softening, jnp.float32))
    if bvh is None:
        lo, hi = scene_bounds(points)
        bvh = build_bvh(points, lo, hi, use_64bit=use_64bit)
    if active is None:
        active = jnp.ones((points.shape[0],), bool)

    def fn(acc, _qi, _j, r2):
        return acc - jax.lax.rsqrt(r2 + soft2), jnp.bool_(False)

    with jax.named_scope("halos.potential_pass"):
        out = query(bvh, within(points.astype(jnp.float32), eps_f), fn,
                    jnp.float32(0.0))
        return jnp.where(active, out, 0.0)


@partial(jax.jit, static_argnames=("capacity", "use_64bit"))
def most_bound_centers(points: jax.Array, particle_halo: jax.Array,
                       eps, *, capacity: int, softening=None,
                       bvh: Bvh | None = None,
                       use_64bit: bool = True) -> MostBoundResult:
    """Per-halo most-bound-particle proxy centers.

    ``particle_halo``: the catalog's (n,) particle→slot map (-1 = no halo).
    Only member particles are queried; empty slots return index -1.
    ``bvh``: optional prebuilt tree over ``points`` (skips the rebuild).
    """
    n = points.shape[0]
    member = particle_halo >= 0
    phi = halo_potentials(points, eps, softening=softening, active=member,
                          bvh=bvh, use_64bit=use_64bit)
    with jax.named_scope("halos.center_argmin"):
        slot = jnp.clip(particle_halo, 0, capacity - 1)
        phi_masked = jnp.where(member, phi, _BIG)
        min_phi = jnp.full((capacity,), _BIG, jnp.float32).at[slot].min(
            phi_masked)
        attains = member & (phi_masked <= min_phi[slot])
        idx = jnp.full((capacity,), n, jnp.int32).at[slot].min(
            jnp.where(attains, jnp.arange(n, dtype=jnp.int32), n))
        found = idx < n
        idx_c = jnp.clip(idx, 0, n - 1)
        center = jnp.where(found[:, None], points[idx_c].astype(jnp.float32),
                           0.0)
    return MostBoundResult(
        index=jnp.where(found, idx, -1),
        center=center,
        potential=jnp.where(found, min_phi, 0.0))
