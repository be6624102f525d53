"""The unified query engine (paper §4.1): one ``query(index, predicates,
callback)`` entry point behind every geometric-search workload.

ArborX's central API story is that all workloads — neighbor counting,
DBSCAN's union passes, kNN, ray casting, interpolation support, halo
analysis — converged on a SINGLE dispatcher with

* **predicates** describing what each query looks for
  (``within(centers, eps)`` spheres with scalar or per-query radii,
  ``intersects_box`` AABB overlap, ``nearest(centers, k)``,
  ``ray(origins, directions)``),
* **fused callbacks** (§4.1.1) executed per predicate-object intersection
  inside the traversal loop, with early exit (§4.1.2,
  ``CallbackTreeTraversalControl``) when the callback reports done,
* **output protocols** on top of the callback machinery: a DEVICE-RESIDENT
  scan-then-scatter CSR (``query_csr_device`` — count, on-device exclusive
  scan, resumable chunked scatter at per-query offsets; jit-traceable, no
  host sync, no dense ``(q, max_count)`` staging), its dynamic-shape host
  convenience ``query_csr``, and a single-pass fixed-capacity variant with
  overflow detection and doubling retry (``query_csr_buffered``, the §4.1
  buffer optimization, retry count observable),
* **traversal backends** (``stackless`` rope / ``stack`` / ``pallas``
  wavefront kernel / ``pair``) selectable per call, and engine-level
  Morton **query sorting** (§4.2.2) so every client inherits
  traversal-coherence improvements at once.

Clients (``knn``, ``raycast``, ``dbscan``, ``correlation``,
``interpolate``, ``emst``, ``halos/*``) are thin wrappers over this
module; the Pallas wavefront-traversal kernel
(``kernels/wavefront.py``) IS one more backend here — ``backend=
"pallas"`` — instead of N bespoke loops: a block of Morton-sorted
queries per grid step advances the rope traversal in lockstep with the
callback fused as the epilogue, and every protocol (counts, fixed
buffers, device CSR) rides it unchanged.

Layering:

* generic single-query traversal cores (``_one_stackless`` /
  ``_one_stack`` — carry-dependent node tests, fused leaf callbacks),
* ``traverse`` / ``traverse_nearest_stack`` — vmapped generic drivers
  (also the substrate for ``core.traversal``'s compatibility shims and
  EMST's component-filtered nearest search),
* ``query`` + ``query_count`` / ``query_fixed`` / ``query_csr`` /
  ``query_csr_buffered`` — the predicate dispatcher and output protocols,
* ``node_reduce`` — generic bottom-up per-node tree reduction (the same
  fixpoint the AABB build uses), for per-node metadata like EMST's
  component intervals.

Callback contract (spatial predicates): ``callback(carry, query_idx,
obj_idx, d2) -> (carry, done)`` is invoked only when the leaf's bounding
volume satisfies the predicate (for point leaves that IS the exact test);
``d2`` is the squared distance from the query geometry to the leaf volume.
``query_idx`` is the row in the predicate arrays (original order even
under ``sort_queries``), ``obj_idx`` the original object index. NOTE:
``nearest`` callbacks differ in the last argument — they receive the
EUCLIDEAN distance (the quantity the k results are ranked and returned
by), not its square.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.bvh import Bvh, SENTINEL
from repro.core.geometry import aabb_aabb_dist2, point_aabb_dist2
from repro.core.morton import morton32, normalize_points, sort_by_morton32
from repro.kernels.pairwise import INTERPRET
from repro.obs.stats import TraversalStats

__all__ = [
    "Within", "IntersectsBox", "Nearest", "Ray",
    "within", "intersects_box", "nearest", "ray",
    "NearestResult", "RayResult", "DeviceCsr", "BufferedCsr",
    "query", "query_count", "query_fixed", "query_csr", "query_csr_device",
    "query_csr_buffered",
    "traverse", "traverse_nearest_stack", "node_reduce",
    "query_sort_permutation",
]

_STACK_DEPTH = 96  # >= max tree depth: 64 code bits + 32 index tie-break bits


# ---------------------------------------------------------------------------
# Predicates
# ---------------------------------------------------------------------------

class Within(NamedTuple):
    """ε-sphere predicates: all objects within ``radii`` of ``centers``."""
    centers: jax.Array   # (q, d)
    radii: jax.Array     # (q,) — per-query radii (scalar eps broadcast)


class IntersectsBox(NamedTuple):
    """AABB-overlap predicates: all objects intersecting [lo, hi]."""
    lo: jax.Array        # (q, d)
    hi: jax.Array        # (q, d)


class Nearest(NamedTuple):
    """k-nearest predicates. ``k`` is static (python int)."""
    centers: jax.Array   # (q, d)
    k: int


class Ray(NamedTuple):
    """Nearest-hit ray predicates (slab method vs leaf volumes)."""
    origins: jax.Array     # (q, d)
    directions: jax.Array  # (q, d)


def within(centers: jax.Array, radii) -> Within:
    """Sphere predicate; ``radii`` is a scalar eps or a (q,) per-query
    vector (e.g. spherical-overdensity searches, ``halos/so_mass.py``)."""
    r = jnp.broadcast_to(jnp.asarray(radii, centers.dtype), (centers.shape[0],))
    return Within(centers=centers, radii=r)


def intersects_box(lo: jax.Array, hi: jax.Array) -> IntersectsBox:
    return IntersectsBox(lo=lo, hi=hi)


def nearest(centers: jax.Array, k: int) -> Nearest:
    return Nearest(centers=centers, k=int(k))


def ray(origins: jax.Array, directions: jax.Array) -> Ray:
    return Ray(origins=origins, directions=directions)


class NearestResult(NamedTuple):
    indices: jax.Array    # (q, k) int32, sorted by distance (-1 = unfilled)
    distances: jax.Array  # (q, k) f32 euclidean


class RayResult(NamedTuple):
    index: jax.Array   # (q,) int32 — original object index (-1 = miss)
    t: jax.Array       # (q,) f32 — entry parameter along the ray


class DeviceCsr(NamedTuple):
    """Device-resident CSR output. ``indices`` is bound-sized (``capacity``);
    ``total`` is the true hit count (a device scalar — may exceed capacity,
    in which case ``overflowed`` is set and surplus hits were dropped).
    ``offsets``/``total`` carry the caller's ``index_dtype`` (int32 by
    default; pass int64 under x64 when total hits can exceed 2^31 — the
    exascale configuration the scale-safety analyzer proves out)."""
    offsets: jax.Array     # (q+1,) index_dtype exclusive-scan row starts
    indices: jax.Array     # (capacity,) int32, -1 padded past ``total``
    total: jax.Array       # () index_dtype
    overflowed: jax.Array  # () bool


def _canon_index_dtype(index_dtype):
    """Validate an offsets dtype. Requesting int64 with x64 disabled is a
    hard error: JAX would silently stage int32 and the cumsum could wrap
    past 2^31 hits (staticcheck rule W1)."""
    dt = jnp.dtype(index_dtype)
    if dt not in (jnp.dtype(jnp.int32), jnp.dtype(jnp.int64)):
        raise ValueError(f"index_dtype must be int32 or int64, got {dt}")
    if dt == jnp.dtype(jnp.int64) and not jax.config.jax_enable_x64:
        raise ValueError(
            "index_dtype=int64 requires x64 mode "
            "(jax.enable_x64(True) or jax_enable_x64=True); "
            "without it JAX silently truncates to int32 and CSR offsets "
            "overflow once total hits exceed 2^31")
    return dt


class BufferedCsr(NamedTuple):
    """Single-pass buffered CSR with observable retry behaviour."""
    offsets: jax.Array   # (q+1,) int32
    indices: jax.Array   # (total,) int32
    attempts: int        # host int — passes taken (1 = zero-retry fast path)
    overflowed: bool     # host bool — whether ANY attempt overflowed


# ---------------------------------------------------------------------------
# Generic traversal cores (single query; carry-dependent node tests)
# ---------------------------------------------------------------------------

def _one_stackless(bvh: Bvh, q, node_fn, leaf_fn, carry0, start):
    """Rope-based stackless walk (§4.2.1): ``left_child`` on hit, ``rope``
    on miss/leaf; a single int32 of traversal state per query."""
    n = bvh.num_leaves

    def cond(state):
        node, _, done = state
        return (node != SENTINEL) & ~done

    def body(state):
        node, carry, done = state
        is_leaf = node >= n - 1
        sorted_idx = node - (n - 1)
        carry_leaf, done_leaf = leaf_fn(
            q, carry, bvh.leaf_perm[jnp.clip(sorted_idx, 0, n - 1)], sorted_idx)
        next_leaf = bvh.rope[node]

        hit = node_fn(q, carry, node)
        node_c = jnp.clip(node, 0, n - 2)
        next_internal = jnp.where(hit, bvh.left_child[node_c], bvh.rope[node])

        carry = jax.tree.map(lambda a, b: jnp.where(is_leaf, a, b), carry_leaf, carry)
        done = jnp.where(is_leaf, done | done_leaf, done)
        node = jnp.where(is_leaf, next_leaf, next_internal)
        return node, carry, done

    _, carry, _ = jax.lax.while_loop(cond, body, (start, carry0, jnp.bool_(False)))
    return carry


def _one_stack(bvh: Bvh, q, node_fn, leaf_fn, carry0):
    """Classic stack-based walk (the Fig. 4 pre-stackless baseline)."""
    n = bvh.num_leaves
    stack0 = jnp.full((_STACK_DEPTH,), SENTINEL, jnp.int32).at[0].set(0)

    def cond(state):
        sp, _, _, done = state
        return (sp > 0) & ~done

    def body(state):
        sp, stack, carry, done = state
        node = stack[sp - 1]
        sp = sp - 1
        is_leaf = node >= n - 1
        sorted_idx = node - (n - 1)

        carry_leaf, done_leaf = leaf_fn(
            q, carry, bvh.leaf_perm[jnp.clip(sorted_idx, 0, n - 1)], sorted_idx)

        hit = node_fn(q, carry, node) & ~is_leaf
        node_c = jnp.clip(node, 0, n - 2)
        # Push right then left so left pops first (matches rope order).
        stack = stack.at[sp].set(jnp.where(hit, bvh.right_child[node_c], stack[sp]))
        sp_r = sp + hit.astype(jnp.int32)
        stack = stack.at[sp_r].set(jnp.where(hit, bvh.left_child[node_c], stack[sp_r]))
        sp = sp_r + hit.astype(jnp.int32)

        carry = jax.tree.map(lambda a, b: jnp.where(is_leaf, a, b), carry_leaf, carry)
        done = done | (is_leaf & done_leaf)
        return sp, stack, carry, done

    _, _, carry, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(1), stack0, carry0, jnp.bool_(False)))
    return carry


# --- stats-instrumented twins of the traversal cores ------------------------
#
# The ``with_stats=`` paths below are SEPARATE functions, not flags inside
# ``_one_stackless``/``_one_stack``: the stats-off path must stage the exact
# jaxpr it staged before the obs layer existed (machine-checked by the
# ``stats_path_identity`` audit in ``repro.staticcheck``), so the original
# cores stay untouched and the instrumented twins pay for their counters only
# when asked for.

def _node_depths(bvh: Bvh) -> jax.Array:
    """Per-node tree depth (root = 0), propagated top-down one level per
    iteration; ``_STACK_DEPTH`` iterations bound any tree this engine can
    traverse. Traced once per stats-on query batch (outside the vmap)."""
    n = bvh.num_leaves
    ids = jnp.arange(max(n - 1, 0), dtype=jnp.int32)

    def body(_, depth):
        d = depth[ids] + 1
        depth = depth.at[bvh.left_child].set(d)
        depth = depth.at[bvh.right_child].set(d)
        return depth

    depth0 = jnp.zeros((2 * n - 1,), jnp.int32)
    return jax.lax.fori_loop(0, _STACK_DEPTH, body, depth0)


def _one_stackless_stats(bvh: Bvh, q, node_fn, leaf_fn, carry0, start, depths):
    """``_one_stackless`` with traversal counters threaded through the loop
    carry. Returns ``(carry, (nodes, aabb_tests, leaf_tests, max_depth,
    early_exit))`` — all device scalars."""
    n = bvh.num_leaves

    def cond(state):
        node, _, done = state[0], state[1], state[2]
        return (node != SENTINEL) & ~done

    def body(state):
        node, carry, done, nodes, aabb, leaf, maxd = state
        is_leaf = node >= n - 1
        sorted_idx = node - (n - 1)
        carry_leaf, done_leaf = leaf_fn(
            q, carry, bvh.leaf_perm[jnp.clip(sorted_idx, 0, n - 1)], sorted_idx)
        next_leaf = bvh.rope[node]

        hit = node_fn(q, carry, node)
        node_c = jnp.clip(node, 0, n - 2)
        next_internal = jnp.where(hit, bvh.left_child[node_c], bvh.rope[node])

        nodes = nodes + 1
        aabb = aabb + (~is_leaf).astype(jnp.int32)
        leaf = leaf + is_leaf.astype(jnp.int32)
        maxd = jnp.maximum(maxd, depths[node])

        carry = jax.tree.map(lambda a, b: jnp.where(is_leaf, a, b), carry_leaf, carry)
        done = jnp.where(is_leaf, done | done_leaf, done)
        node = jnp.where(is_leaf, next_leaf, next_internal)
        return node, carry, done, nodes, aabb, leaf, maxd

    z = jnp.int32(0)
    _, carry, done, nodes, aabb, leaf, maxd = jax.lax.while_loop(
        cond, body, (start, carry0, jnp.bool_(False), z, z, z, z))
    return carry, (nodes, aabb, leaf, maxd, done)


def _one_stack_stats(bvh: Bvh, q, node_fn, leaf_fn, carry0):
    """``_one_stack`` with counters; ``max_depth`` is the stack's high-water
    pointer (the quantity that overflows ``_STACK_DEPTH``)."""
    n = bvh.num_leaves
    stack0 = jnp.full((_STACK_DEPTH,), SENTINEL, jnp.int32).at[0].set(0)

    def cond(state):
        sp, done = state[0], state[3]
        return (sp > 0) & ~done

    def body(state):
        sp, stack, carry, done, nodes, aabb, leaf, maxsp = state
        node = stack[sp - 1]
        sp = sp - 1
        is_leaf = node >= n - 1
        sorted_idx = node - (n - 1)

        carry_leaf, done_leaf = leaf_fn(
            q, carry, bvh.leaf_perm[jnp.clip(sorted_idx, 0, n - 1)], sorted_idx)

        hit = node_fn(q, carry, node) & ~is_leaf
        node_c = jnp.clip(node, 0, n - 2)
        stack = stack.at[sp].set(jnp.where(hit, bvh.right_child[node_c], stack[sp]))
        sp_r = sp + hit.astype(jnp.int32)
        stack = stack.at[sp_r].set(jnp.where(hit, bvh.left_child[node_c], stack[sp_r]))
        sp = sp_r + hit.astype(jnp.int32)

        nodes = nodes + 1
        aabb = aabb + (~is_leaf).astype(jnp.int32)
        leaf = leaf + is_leaf.astype(jnp.int32)
        maxsp = jnp.maximum(maxsp, sp)

        carry = jax.tree.map(lambda a, b: jnp.where(is_leaf, a, b), carry_leaf, carry)
        done = done | (is_leaf & done_leaf)
        return sp, stack, carry, done, nodes, aabb, leaf, maxsp

    z = jnp.int32(0)
    _, _, carry, done, nodes, aabb, leaf, maxsp = jax.lax.while_loop(
        cond, body,
        (jnp.int32(1), stack0, carry0, jnp.bool_(False), z, z, z, jnp.int32(1)))
    return carry, (nodes, aabb, leaf, maxsp, done)


def _stats_from_raw(raw, callback_hits=None) -> TraversalStats:
    """Assemble the (q,)-shaped raw counter columns the vmapped stats cores
    return into a :class:`TraversalStats`."""
    nodes, aabb, leaf, maxd, done = raw
    if callback_hits is None:
        callback_hits = jnp.zeros_like(nodes)
    return TraversalStats(nodes_visited=nodes, aabb_tests=aabb,
                          leaf_tests=leaf, callback_hits=callback_hits,
                          early_exits=done, max_depth=maxd)


def _broadcast_carries(carry_init, q_count: int):
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (q_count,) + jnp.shape(x)), carry_init)


def traverse(bvh: Bvh, qdata, node_fn: Callable, leaf_fn: Callable, carry_init,
             *, backend: str = "stackless", start_nodes: jax.Array | None = None,
             with_stats: bool = False):
    """Generic batched traversal: the substrate every protocol builds on.

    ``qdata``: pytree of per-query arrays (leading dim q); each query's
    slice is passed to the callbacks. ``node_fn(q, carry, node) -> bool``
    decides descent (may read the carry — e.g. best-so-far pruning);
    ``leaf_fn(q, carry, obj_idx, sorted_idx) -> (carry, done)`` runs fused
    on every reached leaf. ``backend``: ``stackless`` | ``stack``.

    ``with_stats=True`` routes through the instrumented twin cores and
    returns ``(carries, TraversalStats)`` — the stats stay on device and
    vmap/shard_map like any carry. ``callback_hits`` is zero here (the
    generic driver has no hit notion; the engine protocols fill it in).
    With the default ``with_stats=False`` this stages the identical jaxpr
    it did before the obs layer existed.
    """
    leaves = jax.tree.leaves(qdata)
    if not leaves:
        raise ValueError("qdata must contain at least one per-query array")
    q_count = leaves[0].shape[0]
    carries = _broadcast_carries(carry_init, q_count)

    if backend == "stackless":
        if start_nodes is None:
            start_nodes = jnp.zeros((q_count,), jnp.int32)
        if with_stats:
            depths = _node_depths(bvh)
            out, raw = jax.vmap(
                lambda q, s, c: _one_stackless_stats(
                    bvh, q, node_fn, leaf_fn, c, s, depths)
            )(qdata, start_nodes, carries)
            return out, _stats_from_raw(raw)
        return jax.vmap(
            lambda q, s, c: _one_stackless(bvh, q, node_fn, leaf_fn, c, s)
        )(qdata, start_nodes, carries)
    if backend == "stack":
        if start_nodes is not None:
            raise ValueError("start_nodes is a stackless/pair-backend feature")
        if with_stats:
            out, raw = jax.vmap(
                lambda q, c: _one_stack_stats(bvh, q, node_fn, leaf_fn, c)
            )(qdata, carries)
            return out, _stats_from_raw(raw)
        return jax.vmap(
            lambda q, c: _one_stack(bvh, q, node_fn, leaf_fn, c)
        )(qdata, carries)
    if backend == "pallas":
        raise ValueError(
            "backend='pallas' is dispatched by the engine entry points "
            "(query/query_count/query_csr_device/...), not the generic "
            "traverse driver: the wavefront kernel must rebuild its "
            "node_fn/leaf_fn closures inside the kernel, which prebuilt "
            "user closures cannot do")
    raise ValueError(f"unknown backend {backend!r} (use 'stackless' or 'stack')")


def traverse_nearest_stack(bvh: Bvh, centers: jax.Array, qdata,
                           push_fn: Callable, leaf_fn: Callable, carry_init):
    """Distance-ordered stack traversal — the nearest-search substrate
    (paper §3.2: "relies on a stack and a priority queue").

    Children are pushed far-first (near child explored first, tightening
    the pruning bound early); ``push_fn(q, carry, child, d2_child) ->
    bool`` gates each push against the carry (e.g. the current k-th best),
    ``leaf_fn(q, carry, obj_idx, d2_leaf) -> carry`` updates the candidate
    buffer. Used by the ``nearest`` predicate and EMST's component-
    filtered nearest-neighbor search.
    """
    n = bvh.num_leaves

    def one(center, q, carry0):
        stack0 = jnp.full((_STACK_DEPTH,), SENTINEL, jnp.int32).at[0].set(0)

        def cond(state):
            sp, *_ = state
            return sp > 0

        def body(state):
            sp, stack, carry = state
            node = stack[sp - 1]
            sp = sp - 1
            is_leaf = node >= n - 1

            sorted_idx = jnp.clip(node - (n - 1), 0, n - 1)
            obj = bvh.leaf_perm[sorted_idx]
            d2_leaf = point_aabb_dist2(center, bvh.node_lo[node], bvh.node_hi[node])
            carry_leaf = leaf_fn(q, carry, obj, d2_leaf)
            carry = jax.tree.map(lambda a, b: jnp.where(is_leaf, a, b), carry_leaf, carry)

            node_c = jnp.clip(node, 0, n - 2)
            left = bvh.left_child[node_c]
            right = bvh.right_child[node_c]
            dl = point_aabb_dist2(center, bvh.node_lo[left], bvh.node_hi[left])
            dr = point_aabb_dist2(center, bvh.node_lo[right], bvh.node_hi[right])
            near = jnp.where(dl <= dr, left, right)
            far = jnp.where(dl <= dr, right, left)
            d_near = jnp.minimum(dl, dr)
            d_far = jnp.maximum(dl, dr)

            push_far = (~is_leaf) & push_fn(q, carry, far, d_far)
            stack = stack.at[sp].set(jnp.where(push_far, far, stack[sp]))
            sp = sp + push_far.astype(jnp.int32)
            push_near = (~is_leaf) & push_fn(q, carry, near, d_near)
            stack = stack.at[sp].set(jnp.where(push_near, near, stack[sp]))
            sp = sp + push_near.astype(jnp.int32)
            return sp, stack, carry

        _, _, carry = jax.lax.while_loop(cond, body, (jnp.int32(1), stack0, carry0))
        return carry

    carries = _broadcast_carries(carry_init, centers.shape[0])
    return jax.vmap(one)(centers, qdata, carries)


def node_reduce(bvh: Bvh, leaf_values, combine: Callable, identity):
    """Bottom-up per-node reduction over the tree (the AABB-build fixpoint,
    generalized): returns a pytree of (2n-1, ...) node values where leaf
    node ``(n-1)+k`` holds ``leaf_values[k]`` (SORTED leaf order) and each
    internal node holds ``combine(left, right)``. Used for per-node
    metadata (e.g. EMST's component intervals)."""
    n = bvh.num_leaves
    ids = jnp.arange(n - 1, dtype=jnp.int32)

    def seed(ident, lv):
        ident_rows = jnp.broadcast_to(jnp.asarray(ident), (n - 1,) + jnp.shape(ident))
        return jnp.concatenate([ident_rows, jnp.asarray(lv)])

    vals0 = jax.tree.map(seed, identity, leaf_values)
    ready0 = jnp.concatenate([jnp.zeros(n - 1, bool), jnp.ones(n, bool)])

    def cond(state):
        _, ready = state
        return ~jnp.all(ready)

    def body(state):
        vals, ready = state
        l, r = bvh.left_child, bvh.right_child
        new = combine(jax.tree.map(lambda x: x[l], vals),
                      jax.tree.map(lambda x: x[r], vals))
        ok = ready[l] & ready[r]

        def upd(v, nv):
            mask = ok.reshape(ok.shape + (1,) * (v.ndim - 1))
            return v.at[ids].set(jnp.where(mask, nv, v[ids]))

        vals = jax.tree.map(upd, vals, new)
        ready = ready.at[ids].set(ready[ids] | ok)
        return vals, ready

    vals, _ = jax.lax.while_loop(cond, body, (vals0, ready0))
    return vals


# ---------------------------------------------------------------------------
# Morton query sorting (§4.2.2)
# ---------------------------------------------------------------------------

def query_sort_permutation(bvh: Bvh, centers: jax.Array) -> jax.Array:
    """Morton-order permutation of query centers over the tree's root AABB
    (queries outside the scene clamp to the boundary bins). Sorting queries
    the same way the leaves are sorted makes consecutive queries traverse
    similar paths — ArborX's query-sorting optimization, here an
    engine-level option every client inherits."""
    unit = normalize_points(centers.astype(jnp.float32),
                            bvh.node_lo[0].astype(jnp.float32),
                            bvh.node_hi[0].astype(jnp.float32))
    return sort_by_morton32(morton32(unit)).astype(jnp.int32)


def _apply_sort(perm, tree_):
    return jax.tree.map(lambda x: jnp.take(x, perm, axis=0), tree_)


def _invert_perm(perm: jax.Array) -> jax.Array:
    return jnp.zeros_like(perm).at[perm].set(
        jnp.arange(perm.shape[0], dtype=perm.dtype))


# ---------------------------------------------------------------------------
# The engine: predicate dispatch + fused-callback protocol
# ---------------------------------------------------------------------------

def _pred_geom(pred):
    """Per-query geometry arrays a spatial predicate contributes to qdata."""
    if isinstance(pred, Within):
        return (pred.centers, pred.radii.astype(pred.centers.dtype) ** 2)
    if isinstance(pred, IntersectsBox):
        return (pred.lo, pred.hi)
    if isinstance(pred, Ray):
        return (pred.origins, pred.directions)
    raise TypeError(f"not a spatial predicate: {type(pred).__name__}")


def _pred_fns(bvh, kind):
    """(node_fn, leaf_aux) for predicate type ``kind`` against ``bvh``.

    ``bvh`` may be the engine's :class:`Bvh` or the wavefront kernel's
    in-kernel ``TreeView`` — the Pallas backend re-invokes this factory
    INSIDE the kernel so the closures capture kernel-local array views
    rather than outer tracers (which a Pallas body must not close over).
    ``leaf_aux`` returns (d2, hit) of a leaf node's bounding volume vs the
    predicate — for point leaves this is the exact point-to-point test.
    """
    n = bvh.num_leaves

    if issubclass(kind, Within):
        def node_fn(q, carry, node):
            (_, center, r2) = q
            return point_aabb_dist2(center, bvh.node_lo[node], bvh.node_hi[node]) <= r2

        def leaf_aux(q, sorted_idx):
            (_, center, r2) = q
            leaf_node = jnp.clip(sorted_idx, 0, n - 1) + (n - 1)
            d2 = point_aabb_dist2(center, bvh.node_lo[leaf_node], bvh.node_hi[leaf_node])
            return d2, d2 <= r2

        return node_fn, leaf_aux

    if issubclass(kind, IntersectsBox):
        def node_fn(q, carry, node):
            (_, qlo, qhi) = q
            return aabb_aabb_dist2(qlo, qhi, bvh.node_lo[node], bvh.node_hi[node]) <= 0.0

        def leaf_aux(q, sorted_idx):
            (_, qlo, qhi) = q
            leaf_node = jnp.clip(sorted_idx, 0, n - 1) + (n - 1)
            d2 = aabb_aabb_dist2(qlo, qhi, bvh.node_lo[leaf_node], bvh.node_hi[leaf_node])
            return d2, d2 <= 0.0

        return node_fn, leaf_aux

    if issubclass(kind, Ray):
        # All-intersections ray mode: the predicate is "the ray's slab test
        # hits the leaf volume"; callbacks receive the ENTRY PARAMETER t in
        # the last argument slot (the quantity the nearest-hit protocol ranks
        # by), not a squared distance.
        def node_fn(q, carry, node):
            (_, origin, direction) = q
            _, hit = _ray_box(origin, _safe_inv(direction),
                              bvh.node_lo[node], bvh.node_hi[node])
            return hit

        def leaf_aux(q, sorted_idx):
            (_, origin, direction) = q
            leaf_node = jnp.clip(sorted_idx, 0, n - 1) + (n - 1)
            t, hit = _ray_box(origin, _safe_inv(direction),
                              bvh.node_lo[leaf_node], bvh.node_hi[leaf_node])
            return t, hit

        return node_fn, leaf_aux

    raise TypeError(f"not a spatial predicate: {kind.__name__}")


def _spatial_fns(bvh: Bvh, pred):
    """(qdata_geom, node_fn, leaf_aux) for a spatial predicate."""
    node_fn, leaf_aux = _pred_fns(bvh, type(pred))
    return _pred_geom(pred), node_fn, leaf_aux


def _fused_leaf_fn(leaf_aux, callback):
    """The engine's fused-callback leaf test: run the predicate's leaf_aux,
    invoke the user callback only on hits, early-exit when it says done.
    One definition shared by the vmapped cores and the wavefront kernel
    (which rebuilds it inside the kernel from a kernel-local leaf_aux)."""
    def leaf_fn(q, carry, obj, sorted_idx):
        d2, hit = leaf_aux(q, sorted_idx)
        carry2, done2 = callback(carry, q[0], obj, d2)
        carry = jax.tree.map(lambda a, b: jnp.where(hit, a, b), carry2, carry)
        return carry, hit & done2
    return leaf_fn


def _fused_leaf_fn_stats(leaf_aux, callback):
    """Stats twin of :func:`_fused_leaf_fn`: augmented carry
    (user_carry, n_hits) — the engine counts fused-callback invocations
    itself, then grafts the column into the stats record."""
    def leaf_fn(q, carry_h, obj, sorted_idx):
        carry, nh = carry_h
        d2, hit = leaf_aux(q, sorted_idx)
        carry2, done2 = callback(carry, q[0], obj, d2)
        carry = jax.tree.map(lambda a, b: jnp.where(hit, a, b), carry2, carry)
        return (carry, nh + hit.astype(jnp.int32)), hit & done2
    return leaf_fn


def _pred_centers(pred):
    if isinstance(pred, (Within, Nearest)):
        return pred.centers
    if isinstance(pred, IntersectsBox):
        return (pred.lo + pred.hi) * 0.5
    return pred.origins


def _spatial_query(bvh, pred, callback, carry_init, backend, sort_queries,
                   with_stats=False, start_nodes=None):
    geom, node_fn, leaf_aux = _spatial_fns(bvh, pred)
    q_count = jax.tree.leaves(geom)[0].shape[0]
    qidx = jnp.arange(q_count, dtype=jnp.int32)
    qdata = (qidx,) + geom

    if sort_queries:
        perm = query_sort_permutation(bvh, _pred_centers(pred))
        qdata = _apply_sort(perm, qdata)
        if start_nodes is not None:
            start_nodes = jnp.take(start_nodes, perm, axis=0)

    if backend == "pallas":
        # Wavefront kernel backend: the factory re-derives node_fn/leaf_fn
        # inside the kernel from its TreeView (a Pallas body must not
        # close over outer traced arrays). ``kind`` (a type) and the
        # engine's own callbacks are capture-safe.
        from repro.kernels.wavefront import wavefront_traverse
        kind = type(pred)
        if with_stats:
            def make_fns_s(tree):
                nf, la = _pred_fns(tree, kind)
                return nf, _fused_leaf_fn_stats(la, callback)

            (out, hits), raw = wavefront_traverse(
                bvh, qdata, make_fns_s, (carry_init, jnp.int32(0)),
                start_nodes=start_nodes, with_stats=True,
                depths=_node_depths(bvh))
            stats = _stats_from_raw(raw, callback_hits=hits)
            if sort_queries:
                inv = _invert_perm(perm)
                out = _apply_sort(inv, out)
                stats = TraversalStats(*_apply_sort(inv, tuple(stats)))
            return out, stats

        def make_fns(tree):
            nf, la = _pred_fns(tree, kind)
            return nf, _fused_leaf_fn(la, callback)

        out = wavefront_traverse(bvh, qdata, make_fns, carry_init,
                                 start_nodes=start_nodes)
        if sort_queries:
            out = _apply_sort(_invert_perm(perm), out)
        return out

    if with_stats:
        leaf_fn_s = _fused_leaf_fn_stats(leaf_aux, callback)
        (out, hits), stats = traverse(
            bvh, qdata, node_fn, leaf_fn_s, (carry_init, jnp.int32(0)),
            backend=backend, start_nodes=start_nodes, with_stats=True)
        stats = stats._replace(callback_hits=hits)
        if sort_queries:
            inv = _invert_perm(perm)
            out = _apply_sort(inv, out)
            stats = TraversalStats(*_apply_sort(inv, tuple(stats)))
        return out, stats

    leaf_fn = _fused_leaf_fn(leaf_aux, callback)
    out = traverse(bvh, qdata, node_fn, leaf_fn, carry_init, backend=backend,
                   start_nodes=start_nodes)
    if sort_queries:
        out = _apply_sort(_invert_perm(perm), out)
    return out


def _pair_query(bvh, pred, callback, carry_init, with_stats=False):
    """Pair traversal (§4.2.3): predicates must be ``within`` over the very
    points the tree indexes; query k starts at ``rope[leaf_k]`` so it
    visits exactly the leaves AFTER k in Morton order — each unordered
    pair once. Carries are returned in SORTED (Morton) query order; row k
    belongs to original point ``bvh.leaf_perm[k]`` (the index passed to
    the callback as ``query_idx``). With ``with_stats`` the stats rows are
    in the same sorted order as the carries."""
    if not isinstance(pred, Within):
        raise TypeError("backend='pair' requires a within(...) predicate over "
                        "the indexed points")
    n = bvh.num_leaves
    if pred.centers.shape[0] != n:
        raise ValueError(
            f"backend='pair' is a self-join: the predicate must cover exactly "
            f"the {n} indexed points, got {pred.centers.shape[0]} queries")
    geom, node_fn, leaf_aux = _spatial_fns(bvh, pred)
    # Query k = sorted point k; its query_idx is the ORIGINAL index leaf_perm[k].
    qdata = (bvh.leaf_perm,) + _apply_sort(bvh.leaf_perm, geom)
    starts = bvh.rope[jnp.arange(n, dtype=jnp.int32) + (n - 1)]

    if with_stats:
        leaf_fn_s = _fused_leaf_fn_stats(leaf_aux, callback)
        (out, hits), stats = traverse(
            bvh, qdata, node_fn, leaf_fn_s, (carry_init, jnp.int32(0)),
            backend="stackless", start_nodes=starts, with_stats=True)
        return out, stats._replace(callback_hits=hits)

    return traverse(bvh, qdata, node_fn, _fused_leaf_fn(leaf_aux, callback),
                    carry_init, backend="stackless", start_nodes=starts)


# --- nearest (priority-queue carry inside the engine) -----------------------

def _nearest_batched(bvh: Bvh, centers: jax.Array, k: int) -> NearestResult:
    """kNN by euclidean distance to leaf bounding volumes (== the points,
    for point leaves): ordered stack + bounded priority queue (paper §3.2).
    The candidate buffer is kept UNSORTED; the worst element is tracked by
    max() and replaced on improvement."""
    def push_fn(q, carry, child, d2):
        dists, _ = carry
        return d2 < jnp.max(dists)

    def leaf_fn(q, carry, obj, d2):
        dists, idxs = carry
        worst = jnp.argmax(dists)
        better = d2 < dists[worst]
        dists = jnp.where(better, dists.at[worst].set(d2), dists)
        idxs = jnp.where(better, idxs.at[worst].set(obj), idxs)
        return dists, idxs

    d0 = jnp.full((k,), jnp.inf, jnp.float32)
    i0 = jnp.full((k,), -1, jnp.int32)
    dists, idxs = traverse_nearest_stack(
        bvh, centers, jnp.zeros((centers.shape[0],), jnp.int8),
        push_fn, leaf_fn, (d0, i0))
    order = jnp.argsort(dists, axis=1)
    return NearestResult(indices=jnp.take_along_axis(idxs, order, axis=1),
                         distances=jnp.sqrt(jnp.take_along_axis(dists, order, axis=1)))


def _nearest_query(bvh, pred: Nearest, callback, carry_init, sort_queries):
    centers = pred.centers
    if sort_queries:
        perm = query_sort_permutation(bvh, centers)
        centers = centers[perm]
    res = _nearest_batched(bvh, centers, pred.k)
    if sort_queries:
        inv = _invert_perm(perm)
        res = NearestResult(indices=res.indices[inv], distances=res.distances[inv])
    if callback is None:
        return res

    # Callback protocol: invoked per result in ascending-distance order,
    # with the EUCLIDEAN distance (unlike spatial callbacks, which get d2).
    q_count = pred.centers.shape[0]

    def one(qidx, idxs, dists, carry0):
        def step(i, state):
            carry, done = state
            carry2, done2 = callback(carry, qidx, idxs[i], dists[i])
            valid = (idxs[i] >= 0) & ~done
            carry = jax.tree.map(lambda a, b: jnp.where(valid, a, b), carry2, carry)
            return carry, done | (valid & done2)

        carry, _ = jax.lax.fori_loop(0, pred.k, step, (carry0, jnp.bool_(False)))
        return carry

    carries = _broadcast_carries(carry_init, q_count)
    return jax.vmap(one)(jnp.arange(q_count, dtype=jnp.int32),
                         res.indices, res.distances, carries)


# --- rays (nearest-hit protocol) --------------------------------------------

def _safe_inv(direction):
    """1/direction with zero components nudged off the axis (slab method)."""
    return 1.0 / jnp.where(jnp.abs(direction) < 1e-12,
                           jnp.sign(direction) * 1e-12 + 1e-12, direction)


def _ray_box(origin, inv_dir, lo, hi):
    """Slab test. Returns (t_entry, hit) with t_entry >= 0."""
    t0 = (lo - origin) * inv_dir
    t1 = (hi - origin) * inv_dir
    tmin = jnp.max(jnp.minimum(t0, t1))
    tmax = jnp.min(jnp.maximum(t0, t1))
    hit = (tmax >= jnp.maximum(tmin, 0.0))
    return jnp.maximum(tmin, 0.0), hit


def _ray_batched(bvh: Bvh, origins: jax.Array, directions: jax.Array) -> RayResult:
    """Nearest leaf-volume hit per ray: ordered stack traversal pruning
    nodes whose entry t exceeds the current best."""
    n = bvh.num_leaves

    def one(origin, direction):
        inv = _safe_inv(direction)
        stack0 = jnp.full((_STACK_DEPTH,), SENTINEL, jnp.int32).at[0].set(0)

        def cond(state):
            return state[0] > 0

        def body(state):
            sp, stack, best_t, best_i = state
            node = stack[sp - 1]
            sp = sp - 1
            is_leaf = node >= n - 1
            t_in, hit = _ray_box(origin, inv, bvh.node_lo[node],
                                 bvh.node_hi[node])
            closer = hit & (t_in < best_t)

            sorted_idx = jnp.clip(node - (n - 1), 0, n - 1)
            orig = bvh.leaf_perm[sorted_idx]
            take = is_leaf & closer
            best_i = jnp.where(take, orig, best_i)
            best_t = jnp.where(take, t_in, best_t)

            node_c = jnp.clip(node, 0, n - 2)
            for child in (bvh.right_child[node_c], bvh.left_child[node_c]):
                tc, hc = _ray_box(origin, inv, bvh.node_lo[child],
                                  bvh.node_hi[child])
                push = (~is_leaf) & closer & hc & (tc < best_t)
                stack = stack.at[sp].set(jnp.where(push, child, stack[sp]))
                sp = sp + push.astype(jnp.int32)
            return sp, stack, best_t, best_i

        _, _, best_t, best_i = jax.lax.while_loop(
            cond, body, (jnp.int32(1), stack0, jnp.float32(jnp.inf),
                         jnp.int32(-1)))
        return best_i, best_t

    idx, t = jax.vmap(one)(origins, directions)
    return RayResult(index=idx, t=t)


def _ray_query(bvh, pred: Ray, callback, sort_queries):
    """Nearest-hit protocol (callback=None). With a callback, rays dispatch
    through the spatial path instead — the ALL-INTERSECTIONS protocol."""
    origins, directions = pred.origins, pred.directions
    if sort_queries:
        perm = query_sort_permutation(bvh, origins)
        origins, directions = origins[perm], directions[perm]
    res = _ray_batched(bvh, origins, directions)
    if sort_queries:
        inv = _invert_perm(perm)
        res = RayResult(index=res.index[inv], t=res.t[inv])
    return res


def query(bvh: Bvh, predicates, callback: Callable | None = None,
          carry_init=None, *, backend: str = "stackless",
          sort_queries: bool = False, with_stats: bool = False,
          start_nodes: jax.Array | None = None):
    """The single entry point (§4.1): dispatch ``predicates`` against the
    tree, fusing ``callback`` into the traversal.

    * ``Within`` / ``IntersectsBox`` + callback -> per-query final carries.
      ``backend``: ``stackless`` | ``stack`` | ``pallas`` (the wavefront
      kernel — a block of queries per grid step advances the rope
      traversal in lockstep; interpret mode off the TPU, and on a TPU a
      ``NotImplementedError`` naming the missing Mosaic lowering) |
      ``pair`` (self-join; carries in sorted leaf order, see
      ``_pair_query``).
    * ``Nearest`` -> ``NearestResult`` (or carries, if a callback is given:
      invoked per result in ascending-distance order).
    * ``Ray`` without callback -> ``RayResult`` (nearest hit). With a
      callback, rays run the ALL-INTERSECTIONS protocol: the callback fires
      per leaf volume the ray pierces, with the entry parameter ``t`` in the
      last argument (so every output protocol — counts, fixed buffers, CSR —
      works on rays too).

    ``sort_queries=True`` Morton-sorts queries against the tree's scene
    bounds before traversal and unsorts the outputs (§4.2.2) — results are
    positionally identical, traversal is more coherent.

    ``with_stats=True`` (spatial predicates with a callback only) returns
    ``(result, TraversalStats)`` — per-query device-side traversal
    counters, see ``repro.obs.stats``. Off by default; the default path
    stages the identical jaxpr it did before the obs layer existed.

    ``start_nodes`` (stackless/pallas spatial traversals only) overrides
    the per-query traversal entry node — the cell-grid pruned variants
    start queries below the root.
    """
    if backend == "pallas":
        # Raise here, before any tracing, where the kernel would compile
        # natively (a TPU): it has no Mosaic lowering yet.
        from repro.kernels.wavefront import require_lowering
        require_lowering(INTERPRET)
    if with_stats and (isinstance(predicates, Nearest)
                       or (isinstance(predicates, Ray) and callback is None)):
        raise ValueError(
            "with_stats instruments the spatial traversal cores; the "
            "nearest / nearest-hit-ray protocols run on the priority-queue "
            "substrate, which has no stats threading")
    if start_nodes is not None and (
            isinstance(predicates, Nearest)
            or (isinstance(predicates, Ray) and callback is None)
            or backend == "pair"):
        raise ValueError(
            "start_nodes applies to the spatial stackless/pallas traversals; "
            "the nearest protocols have their own ordering and the pair "
            "backend derives its own start nodes")
    if isinstance(predicates, Nearest):
        return _nearest_query(bvh, predicates, callback, carry_init, sort_queries)
    if isinstance(predicates, Ray):
        if callback is None:
            return _ray_query(bvh, predicates, None, sort_queries)
        if backend == "pair":
            raise ValueError("backend='pair' is a within() self-join")
        return _spatial_query(bvh, predicates, callback, carry_init, backend,
                              sort_queries, with_stats, start_nodes)
    if not isinstance(predicates, (Within, IntersectsBox)):
        raise TypeError(f"unknown predicate type {type(predicates).__name__}")
    if callback is None:
        raise ValueError("spatial predicates need a callback; use "
                         "query_count/query_csr for built-in output protocols")
    if backend == "pair":
        if sort_queries:
            raise ValueError("backend='pair' queries are inherently "
                             "Morton-sorted; sort_queries does not apply")
        return _pair_query(bvh, predicates, callback, carry_init, with_stats)
    return _spatial_query(bvh, predicates, callback, carry_init, backend,
                          sort_queries, with_stats, start_nodes)


# ---------------------------------------------------------------------------
# Output protocols on top of the callback machinery
# ---------------------------------------------------------------------------

def query_count(bvh: Bvh, predicates, *, stop_at: int | None = None,
                backend: str = "stackless", sort_queries: bool = False,
                with_stats: bool = False,
                start_nodes: jax.Array | None = None) -> jax.Array:
    """Per-query intersection counts. ``stop_at`` enables early termination
    (§4.1.2): counting stops (and saturates) at ``stop_at`` — DBSCAN's
    minPts core test needs no exact counts beyond it. ``with_stats=True``
    returns ``(counts, TraversalStats)``."""
    if backend == "pair":
        raise ValueError("output protocols are per-query; the pair backend's "
                         "half-counts need a callback (use query(...))")

    def cb(count, qidx, obj, d2):
        count = count + 1
        done = jnp.bool_(False) if stop_at is None else count >= stop_at
        return count, done

    return query(bvh, predicates, cb, jnp.int32(0), backend=backend,
                 sort_queries=sort_queries, with_stats=with_stats,
                 start_nodes=start_nodes)


def query_fixed(bvh: Bvh, predicates, capacity: int, *,
                backend: str = "stackless", sort_queries: bool = False):
    """Single-pass fixed-capacity output: per-query index buffers
    ``(q, capacity)`` (-1 padded; surplus hits overwrite the last slot),
    true counts ``(q,)``, and an overflow flag ``any(counts > capacity)``.
    The §4.1 buffer-optimization primitive — see ``query_csr_buffered``
    for the doubling retry loop."""
    if backend == "pair":
        raise ValueError("output protocols are per-query; the pair backend's "
                         "half-lists need a callback (use query(...))")

    def cb(carry, qidx, obj, d2):
        buf, cnt = carry
        slot = jnp.clip(cnt, 0, capacity - 1)
        buf = buf.at[slot].set(obj)
        return (buf, cnt + 1), jnp.bool_(False)

    buf0 = jnp.full((capacity,), -1, jnp.int32)
    buf, counts = query(bvh, predicates, cb, (buf0, jnp.int32(0)),
                        backend=backend, sort_queries=sort_queries)
    return buf, counts, jnp.any(counts > capacity)


def _compact_csr(buf: jax.Array, counts: jax.Array,
                 index_dtype=jnp.int32):
    """Scatter per-query buffers (q, cap) into CSR (offsets, indices)."""
    idx_dt = _canon_index_dtype(index_dtype)
    q, cap = buf.shape
    offsets = jnp.concatenate([jnp.zeros((1,), idx_dt),
                               jnp.cumsum(counts, dtype=idx_dt)])
    total = int(offsets[-1]) if q else 0
    pos = offsets[:-1, None] + jnp.arange(cap, dtype=idx_dt)[None, :]
    valid = jnp.arange(cap)[None, :] < counts[:, None]
    # invalid lanes write to a trash slot past the end
    indices = jnp.full((total + 1,), -1, jnp.int32).at[
        jnp.where(valid, pos, total)].set(buf)[:total]
    return offsets, indices


def _csr_fill(bvh: Bvh, pred, offsets: jax.Array, capacity: int, *,
              chunk: int, backend: str, sort_queries: bool) -> jax.Array:
    """Pass 2 of the device-resident protocol: RESUMABLE chunked
    scatter-fill. Each query carries its paused traversal state (one int32
    node pointer for the rope backend, (sp, stack) for the stack backend);
    per outer round every live query collects up to ``chunk`` hits, which
    are scattered straight to ``offsets[q] + slot`` in the shared
    total-size buffer. Staging memory is O(q * chunk), never
    ``(q, max_count)``, and traversal work is not repeated across rounds —
    each round resumes exactly where the last one paused. All control flow
    is ``lax.while_loop``: no host sync anywhere."""
    geom, node_fn, leaf_aux = _spatial_fns(bvh, pred)
    q_count = jax.tree.leaves(geom)[0].shape[0]
    qdata = (jnp.arange(q_count, dtype=jnp.int32),) + geom
    if sort_queries:
        perm = query_sort_permutation(bvh, _pred_centers(pred))
        qdata = _apply_sort(perm, qdata)
    n = bvh.num_leaves
    chunk = max(int(chunk), 1)
    out0 = jnp.full((capacity + 1,), -1, jnp.int32)  # last slot = trash
    if q_count == 0:
        return out0[:capacity]
    # Output segment start per traversal lane (original-order offsets).
    base = offsets[:-1][qdata[0]]

    def record(q, buf, nh, node):
        is_leaf = node >= n - 1
        sorted_idx = jnp.clip(node - (n - 1), 0, n - 1)
        _, hit = leaf_aux(q, sorted_idx)
        take = is_leaf & hit
        buf = jnp.where(
            take, buf.at[jnp.clip(nh, 0, chunk - 1)].set(
                bvh.leaf_perm[sorted_idx]), buf)
        return buf, nh + take.astype(jnp.int32), is_leaf

    if backend == "pallas":
        # Wavefront rounds: one kernel launch per chunk round advances every
        # lane up to `chunk` hits; the factory rebuilds the predicate
        # closures inside the kernel (Pallas bodies must not capture outer
        # tracers). Same resumable int32 node cursor as the rope backend.
        from repro.kernels.wavefront import wavefront_fill_round
        kind = type(pred)
        state0 = jnp.zeros((q_count,), jnp.int32)

        def live(state):
            return state != SENTINEL

        def round_all(state):
            return wavefront_fill_round(
                bvh, qdata, lambda tree: _pred_fns(tree, kind), state, chunk)
    elif backend == "stackless":
        state0 = jnp.zeros((q_count,), jnp.int32)

        def live(state):
            return state != SENTINEL

        def round_one(q, node0):
            def cond(s):
                node, _, nh = s
                return (node != SENTINEL) & (nh < chunk)

            def body(s):
                node, buf, nh = s
                buf, nh, is_leaf = record(q, buf, nh, node)
                node_c = jnp.clip(node, 0, n - 2)
                descend = node_fn(q, None, node)
                node = jnp.where(
                    is_leaf, bvh.rope[node],
                    jnp.where(descend, bvh.left_child[node_c],
                              bvh.rope[node]))
                return node, buf, nh

            node, buf, nh = jax.lax.while_loop(
                cond, body,
                (node0, jnp.full((chunk,), -1, jnp.int32), jnp.int32(0)))
            return node, buf, nh
    elif backend == "stack":
        state0 = (jnp.ones((q_count,), jnp.int32),
                  jnp.full((q_count, _STACK_DEPTH), SENTINEL,
                           jnp.int32).at[:, 0].set(0))

        def live(state):
            return state[0] > 0

        def round_one(q, st0):
            def cond(s):
                sp, _, _, nh = s
                return (sp > 0) & (nh < chunk)

            def body(s):
                sp, stack, buf, nh = s
                node = stack[sp - 1]
                sp = sp - 1
                buf, nh, is_leaf = record(q, buf, nh, node)
                descend = node_fn(q, None, node) & ~is_leaf
                node_c = jnp.clip(node, 0, n - 2)
                stack = stack.at[sp].set(
                    jnp.where(descend, bvh.right_child[node_c], stack[sp]))
                sp_r = sp + descend.astype(jnp.int32)
                stack = stack.at[sp_r].set(
                    jnp.where(descend, bvh.left_child[node_c], stack[sp_r]))
                return sp_r + descend.astype(jnp.int32), stack, buf, nh

            sp, stack, buf, nh = jax.lax.while_loop(
                cond, body, (st0[0], st0[1],
                             jnp.full((chunk,), -1, jnp.int32), jnp.int32(0)))
            return (sp, stack), buf, nh
    else:
        raise ValueError(f"unknown backend {backend!r} for the device CSR "
                         "path (use 'stackless', 'stack' or 'pallas')")

    if backend != "pallas":
        def round_all(state):
            return jax.vmap(round_one)(qdata, state)

    lane = jnp.arange(chunk, dtype=jnp.int32)[None, :]

    def cond(loop):
        state, _, _ = loop
        return jnp.any(live(state))

    def body(loop):
        state, emitted, out = loop
        state, bufs, nhs = round_all(state)
        pos = (base + emitted)[:, None] + lane
        ok = (lane < nhs[:, None]) & (pos < capacity)
        out = out.at[jnp.where(ok, pos, capacity).reshape(-1)] \
            .set(bufs.reshape(-1))
        return state, emitted + nhs, out

    _, _, out = jax.lax.while_loop(
        cond, body, (state0, jnp.zeros((q_count,), jnp.int32), out0))
    return out[:capacity]


def query_csr_device(bvh: Bvh, predicates, capacity: int, *, counts=None,
                     chunk: int = 32, backend: str = "stackless",
                     sort_queries: bool = False,
                     index_dtype=jnp.int32) -> DeviceCsr:
    """Fully DEVICE-RESIDENT scan-then-scatter CSR (the ArborX 2.0
    count-then-fill backbone, with no host round-trip): pass 1 counts per
    predicate, an on-device exclusive scan produces per-query offsets, and
    pass 2's fused traversal scatters hits directly at ``offsets[q] + slot``
    into one total-size buffer of static bound ``capacity``.

    jit-traceable end-to-end — there is NO Python-level sync of device
    values between the count and fill passes, and no dense
    ``(q, max_count)`` staging buffer (staging is O(q * chunk)). Returns
    ``DeviceCsr(offsets, indices, total, overflowed)``; hits past
    ``capacity`` are dropped and flagged. ``counts`` may be passed to reuse
    a precomputed pass 1. ``index_dtype`` sets the offsets/total dtype —
    int64 (under x64) once total hits can exceed 2^31."""
    if backend == "pair":
        raise ValueError("output protocols are per-query; the pair backend's "
                         "half-lists need a callback (use query(...))")
    idx_dt = _canon_index_dtype(index_dtype)
    capacity = max(int(capacity), 0)
    if counts is None:
        counts = query_count(bvh, predicates, backend=backend,
                             sort_queries=sort_queries)
    offsets = jnp.concatenate([jnp.zeros((1,), idx_dt),
                               jnp.cumsum(counts, dtype=idx_dt)])
    indices = _csr_fill(bvh, predicates, offsets, capacity, chunk=chunk,
                        backend=backend, sort_queries=sort_queries)
    total = offsets[-1]
    return DeviceCsr(offsets=offsets, indices=indices, total=total,
                     overflowed=total > capacity)


def query_csr(bvh: Bvh, predicates, *, capacity: int | None = None,
              chunk: int = 32, backend: str = "stackless",
              sort_queries: bool = False, index_dtype=jnp.int32) -> DeviceCsr:
    """Count-then-fill CSR output (§4.1), device-resident. With
    ``capacity`` given this IS ``query_csr_device`` (jit-traceable, zero
    host syncs). With ``capacity=None`` (the dynamic-shape convenience,
    host-side only) the exact total sizes ``indices`` — the one
    unavoidable sync for a data-dependent output shape; the count and fill
    passes themselves still never stage a dense ``(q, max_count)`` buffer.

    Returns ``DeviceCsr(offsets (q+1,), indices, total, overflowed)`` with
    per-query indices in traversal order; ``overflowed`` is always False on
    the exact-size path. Handles empty predicate sets (q == 0: offsets is
    ``[0]``, indices empty)."""
    if capacity is not None:
        return query_csr_device(bvh, predicates, capacity, chunk=chunk,
                                backend=backend, sort_queries=sort_queries,
                                index_dtype=index_dtype)
    counts = query_count(bvh, predicates, backend=backend,
                         sort_queries=sort_queries)
    exact = int(jnp.sum(counts)) if counts.shape[0] else 0
    return query_csr_device(bvh, predicates, exact, counts=counts,
                            chunk=chunk, backend=backend,
                            sort_queries=sort_queries,
                            index_dtype=index_dtype)


def query_csr_buffered(bvh: Bvh, predicates, *, capacity: int = 8,
                       max_doublings: int = 16, backend: str = "stackless",
                       sort_queries: bool = False) -> BufferedCsr:
    """Single-pass CSR with the §4.1 buffer optimization: optimistically
    fill fixed per-query buffers of ``capacity``; if ANY query overflows,
    double and retry (each retry is one pass — the common case is zero
    retries, beating the two-pass protocol by ~2x when the guess holds).
    Host-driven by construction (each retry decision is a sync). Returns
    ``BufferedCsr(offsets, indices, attempts, overflowed)`` — the retry
    count is observable, not silent: ``attempts == 1`` is the zero-retry
    fast path, ``overflowed`` reports whether any pass overflowed."""
    cap = max(int(capacity), 1)
    overflowed_any = False
    for attempt in range(1, max_doublings + 2):
        buf, counts, overflow = query_fixed(bvh, predicates, cap,
                                            backend=backend,
                                            sort_queries=sort_queries)
        if not bool(overflow):
            offsets, indices = _compact_csr(buf, counts)
            return BufferedCsr(offsets=offsets, indices=indices,
                               attempts=attempt, overflowed=overflowed_any)
        overflowed_any = True
        cap *= 2
    raise RuntimeError(f"query_csr_buffered: still overflowing at capacity {cap}")
