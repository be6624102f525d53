"""Linear BVH construction in pure JAX (paper §4.2.1).

Construction follows Karras (2012): every internal node's leaf range is a
purely per-node function of the Morton-code ``delta`` operator, so the whole
hierarchy builds with one ``vmap`` — the functional analogue of the
GPU-parallel build. ArborX switched to Apetrei (2014) for construction speed
and then *recovered Karras' node ordering* to keep rope-based stackless
traversal (Prokopenko & Lebrun-Grandié 2024); here both formulations reduce to
the same range arithmetic, which we exploit to compute ropes (escape indices)
in closed form instead of a second bottom-up pass:

  For a node whose leaf range ends at ``l`` (l < n-1), the lowest ancestor
  that contains leaf ``l+1`` is the unique internal node P whose split is at
  ``l`` (split positions are a permutation of 0..n-2). The rope is P's right
  child: ``leaf(l+1)`` if P's range ends at ``l+1`` else ``internal(l+1)``.
  Nodes ending at ``n-1`` rope to the sentinel.

Node numbering (ArborX convention): internal nodes are ``0 .. n-2`` (root is
0), leaf k (in Morton-sorted order) is node ``(n-1) + k``. ``SENTINEL = -1``.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import morton as _morton

SENTINEL = jnp.int32(-1)

__all__ = ["Bvh", "build_bvh", "SENTINEL"]


class Bvh(NamedTuple):
    """Array-of-structs LBVH. n leaves, n-1 internal nodes, ids per module doc."""

    # Sorted leaf order: permutation from sorted leaf k -> original point index.
    leaf_perm: jax.Array          # (n,) int32
    # Children of internal nodes (node ids). (n-1,)
    left_child: jax.Array
    right_child: jax.Array
    # Escape indices for ALL nodes (internal 0..n-2 then leaves n-1..2n-2).
    rope: jax.Array               # (2n-1,) int32
    # AABBs for all nodes, same indexing as rope.
    node_lo: jax.Array            # (2n-1, d)
    node_hi: jax.Array            # (2n-1, d)
    # Leaf range (inclusive) covered by each internal node. (n-1,)
    range_left: jax.Array
    range_right: jax.Array

    @property
    def num_leaves(self) -> int:
        return self.leaf_perm.shape[0]

    def leaf_node_id(self, k: jax.Array) -> jax.Array:
        return k + (self.num_leaves - 1)


def _karras_ranges(delta):
    """Given delta(i, j) -> int (vectorized over i), compute per-internal-node
    (range_left, range_right, split) with Karras' doubling + binary search."""

    def per_node(i):
        d = jnp.sign(delta(i, i + 1) - delta(i, i - 1)).astype(jnp.int32)
        d = jnp.where(d == 0, jnp.int32(1), d)  # ties only possible interiorly
        delta_min = delta(i, i - d)

        # Exponential search for the range-length upper bound.
        def cond_up(lm):
            return delta(i, i + lm * d) > delta_min

        l_max = jax.lax.while_loop(cond_up, lambda lm: lm * 2, jnp.int32(2))

        # Binary search the exact other end.
        def bin_step(carry, _):
            l, t = carry
            go = delta(i, i + (l + t) * d) > delta_min
            l = jnp.where(go & (t > 0), l + t, l)
            return (l, t // 2), None

        # l_max <= 2n so 32 halvings always reach t == 0.
        (l, _), _ = jax.lax.scan(bin_step, (jnp.int32(0), l_max // 2), None, length=32)
        j = i + l * d

        # Split search: find largest s with delta(i, i + (s+t)*d) > delta_node.
        delta_node = delta(i, j)

        def split_step(carry, _):
            s, t = carry
            t_here = (t + 1) // 2  # ceil halving sequence
            go = delta(i, i + (s + t_here) * d) > delta_node
            s = jnp.where(go & (t > 0), s + t_here, s)
            t = jnp.where(t > 1, t_here, jnp.int32(0))
            return (s, t), None

        (s, _), _ = jax.lax.scan(split_step, (jnp.int32(0), l), None, length=32)
        gamma = i + s * d + jnp.minimum(d, 0)

        first = jnp.minimum(i, j)
        last = jnp.maximum(i, j)
        return first, last, gamma

    return per_node


@partial(jax.jit, static_argnames=("use_64bit",))
def build_bvh(points: jax.Array, scene_lo: jax.Array, scene_hi: jax.Array,
              use_64bit: bool = True) -> Bvh:
    """Build an LBVH over (n, 3) float32 points (leaf AABB = point)."""
    return build_bvh_objects(points, points, scene_lo, scene_hi, use_64bit=use_64bit)


@partial(jax.jit, static_argnames=("use_64bit",))
def build_bvh_objects(leaf_lo: jax.Array, leaf_hi: jax.Array,
                      scene_lo: jax.Array, scene_hi: jax.Array,
                      use_64bit: bool = True) -> Bvh:
    """Build an LBVH over boxed objects (paper §4.3.4 mixed cells+points tree:
    'it only requires bounding volumes for a set of objects'). Morton codes are
    taken from box centers. n must be >= 2."""
    n = leaf_lo.shape[0]
    with jax.named_scope("bvh.build"):
        with jax.named_scope("bvh.morton_sort"):
            centers = (leaf_lo + leaf_hi) * 0.5
            unit = _morton.normalize_points(centers, scene_lo, scene_hi)

            if use_64bit:
                hi, lo = _morton.morton64(unit)
                perm = _morton.sort_by_morton64(hi, lo).astype(jnp.int32)
                hi_s, lo_s = hi[perm], lo[perm]

                def delta(i, j):
                    return _morton.common_prefix_length64(hi_s, lo_s, jnp.asarray(i), jnp.asarray(j))
            else:
                codes = _morton.morton32(unit)
                perm = _morton.sort_by_morton32(codes).astype(jnp.int32)
                codes_s = codes[perm]

                def delta(i, j):
                    return _morton.common_prefix_length32(codes_s, jnp.asarray(i), jnp.asarray(j))

        with jax.named_scope("bvh.hierarchy"):
            internal_ids = jnp.arange(n - 1, dtype=jnp.int32)
            first, last, gamma = jax.vmap(_karras_ranges(delta))(internal_ids)

            # Children: leaf if the child range is a single leaf.
            left = jnp.where(first == gamma, gamma + (n - 1), gamma)
            right = jnp.where(last == gamma + 1, gamma + 1 + (n - 1), gamma + 1)

            # --- Ropes in closed form (see module docstring). ---
            # split_node[g] = internal node whose split position is g.
            split_node = jnp.zeros((n - 1,), jnp.int32).at[gamma].set(internal_ids)
            split_end = jnp.zeros((n - 1,), jnp.int32).at[gamma].set(last)

            def rope_of(end):  # end = inclusive leaf-range end of the node
                is_last = end >= n - 1
                end_c = jnp.clip(end, 0, n - 2)
                p_end = split_end[end_c]
                r = jnp.where(p_end == end + 1, end + 1 + (n - 1), end + 1)
                return jnp.where(is_last, SENTINEL, r).astype(jnp.int32)

            rope_internal = rope_of(last)
            rope_leaf = rope_of(jnp.arange(n, dtype=jnp.int32))
            rope = jnp.concatenate([rope_internal, rope_leaf])

            # --- AABBs: leaves from points, internal via bottom-up fixpoint. ---
            dim = leaf_lo.shape[1]
            big = jnp.full((n - 1, dim), jnp.inf, leaf_lo.dtype)
            node_lo0 = jnp.concatenate([big, leaf_lo[perm]])
            node_hi0 = jnp.concatenate([-big, leaf_hi[perm]])
            ready0 = jnp.concatenate([jnp.zeros(n - 1, bool), jnp.ones(n, bool)])

            def fix_cond(state):
                _, _, ready = state
                return ~jnp.all(ready)

            def fix_body(state):
                nlo, nhi, ready = state
                l_lo, l_hi, l_rdy = nlo[left], nhi[left], ready[left]
                r_lo, r_hi, r_rdy = nlo[right], nhi[right], ready[right]
                new_lo = jnp.minimum(l_lo, r_lo)
                new_hi = jnp.maximum(l_hi, r_hi)
                ok = l_rdy & r_rdy
                nlo = nlo.at[internal_ids].set(jnp.where(ok[:, None], new_lo, nlo[internal_ids]))
                nhi = nhi.at[internal_ids].set(jnp.where(ok[:, None], new_hi, nhi[internal_ids]))
                ready = ready.at[internal_ids].set(ready[internal_ids] | ok)
                return nlo, nhi, ready

            node_lo, node_hi, _ = jax.lax.while_loop(fix_cond, fix_body, (node_lo0, node_hi0, ready0))

    return Bvh(
        leaf_perm=perm,
        left_child=left,
        right_child=right,
        rope=rope,
        node_lo=node_lo,
        node_hi=node_hi,
        range_left=first,
        range_right=last,
    )
