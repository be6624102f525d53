"""DBSCAN variants from the paper (§4.3), faithful tier in pure JAX.

Every ε-search here — neighbor counts with minPts early exit, the
min-label union passes, graph_cc's bounded neighbor buffers, pair
capture — is one ``core.query`` engine call (``within`` predicates +
fused callbacks / the fixed-capacity output protocol / the pair
backend); this module only contributes the clustering logic around it.

The min-label pass (``min_core_label_on``) traverses only the lanes its
mask selects: they are compacted in tree order and walked in chunks of
``LANE_CHUNK`` lanes, each chunk's lockstep loop ending at its own
longest lane. Masked-out lanes are never traversed.

Variants, matching the Fig. 4 improvement ladder:

* ``dbscan_graph_cc``   — initial implementation (§4.3.1): materialize the
  ε-adjacency graph (bounded neighbor buffers — the paper's documented memory
  drawback), then run connected components (ECL-CC analogue).
* ``fdbscan``           — "fused" DBSCAN (§4.3.3): no neighbor storage.
  Phase 1 counts ε-neighbors with EARLY TERMINATION at minPts (§4.1.2);
  Phase 2 runs min-label hook+compress rounds where each round's candidate
  labels come straight from a fused traversal callback (§4.1.1), O(n) memory.
* ``fdbscan_pair``      — FDBSCAN whose union phase uses PAIR TRAVERSAL
  (§4.2.3, improvement (7)): each unordered pair (i, j), i<j in Morton order,
  is visited exactly once; cross-root pairs are captured into a small
  per-query buffer and hooked. Buffer overflow is legal: every overflowing
  round strictly reduces the number of components, so the outer fixpoint
  terminates.
* ``fdbscan_densebox``  — FDBSCAN-DenseBox (§4.3.4): mixed BVH over dense
  ε/√d cells + outside points; dense-cell points are pre-classified core and
  pre-unioned, intra-cell distance tests are eliminated, and a whole cell
  within ε of a query is processed wholesale.

All return int32 labels: core/border points carry their cluster root (the
minimum original index in the component), noise = -1. Cluster-partition
semantics are validated against ``ref_numpy.dbscan_ref``.

Union-find note (DESIGN.md deviation 3): ArborX's ECL-CC uses atomic CAS
hooking; XLA has no atomic CAS, so unions are expressed as deterministic
scatter-min hooking + pointer jumping (same disjoint-set family).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import union_find
from repro.core.bvh import SENTINEL, Bvh, build_bvh, build_bvh_objects
from repro.core.cell_grid import CellGrid, build_cell_grid, cell_box
from repro.core.geometry import scene_bounds as _scene
from repro.core.query import query, query_count, query_fixed, within

NOISE = jnp.int32(-1)

# Lanes per chunk of a min-label pass: each chunk's lockstep loop runs to
# its own longest lane. Chosen from a chip probe of the pass time against
# the chunk size (PERF.md, PR 14).
LANE_CHUNK = 256

__all__ = [
    "NOISE",
    "LANE_CHUNK",
    "DbscanResult",
    "count_neighbors",
    "min_core_label_on",
    "traversed_lanes",
    "union_rounds",
    "dbscan_graph_cc",
    "fdbscan",
    "fdbscan_pair",
    "fdbscan_densebox",
]


class DbscanResult(NamedTuple):
    labels: jax.Array       # (n,) int32; cluster root or -1 (noise)
    core_mask: jax.Array    # (n,) bool
    num_rounds: jax.Array   # () int32 — union fixpoint rounds taken
    lane_share: jax.Array   # () float32 — lanes the min-label passes ran
    #                         over (passes x n); 1.0 where not counted


# ---------------------------------------------------------------------------
# Neighbor counting (phase 1) — fused callback + early termination (§4.1.2)
# ---------------------------------------------------------------------------

def count_neighbors(bvh: Bvh, points: jax.Array, queries: jax.Array, eps,
                    min_pts: int | None = None, use_stack: bool = False) -> jax.Array:
    """ε-neighbor counts for each query (neighborhood includes the point
    itself). With ``min_pts`` set, counting STOPS at min_pts (early
    termination; returned counts saturate there). ``points`` is kept in
    the signature for backward compatibility — the engine tests against
    leaf volumes directly."""
    return query_count(bvh, within(queries, jnp.asarray(eps, points.dtype)),
                       stop_at=min_pts,
                       backend="stack" if use_stack else "stackless")


def _core_mask(bvh, points, eps, min_pts, early_stop=True, use_stack=False):
    with jax.named_scope("dbscan.core_pass"):
        counts = count_neighbors(bvh, points, points, eps,
                                 min_pts=min_pts if early_stop else None,
                                 use_stack=use_stack)
        return counts >= min_pts


# ---------------------------------------------------------------------------
# Min-label candidate traversal (shared by fdbscan variants)
# ---------------------------------------------------------------------------

def _lane_chunk(n: int) -> int:
    return max(min(LANE_CHUNK, n), 1)


def traversed_lanes(queries_mask: jax.Array) -> jax.Array:
    """Lanes a min-label pass over ``queries_mask`` runs: the selected
    lanes rounded up to whole chunks (int32)."""
    c = _lane_chunk(queries_mask.shape[0])
    active = jnp.sum(queries_mask, dtype=jnp.int32)
    return (active + c - 1) // c * c


def min_core_label_on(bvh: Bvh, query_pts: jax.Array, eps, obj_labels,
                      obj_core, queries_mask, sentinel,
                      order: jax.Array | None = None) -> jax.Array:
    """Engine pass shared by the FDBSCAN variants AND the distributed layer:
    for each query point with ``queries_mask`` set, the min over core
    ε-neighbor OBJECTS j of ``obj_labels[j]`` (``sentinel`` if none).

    ``obj_labels`` / ``obj_core`` are indexed by the TREE's object index —
    decoupled from the query set, so the distributed layer can run local
    queries against a local ∪ ghost tree with exchanged ghost labels.
    The sentinel follows ``obj_labels``'s dtype (int64 global ids at scale).

    Masked-out queries are not traversed: a stable partition of ``order``
    (default: the given order) puts the selected queries first, and they
    are walked ``LANE_CHUNK`` at a time, each chunk's lockstep loop ending
    at its own longest lane. Padding lanes of the last chunk start at
    ``SENTINEL`` and never run. Masked-out slots hold ``sentinel``."""
    sentinel = jnp.asarray(sentinel, getattr(obj_labels, "dtype", jnp.int32))
    n = query_pts.shape[0]
    c = _lane_chunk(n)
    padded = -(-n // c) * c
    eps_q = jnp.asarray(eps, query_pts.dtype)

    def fn(best, _qi, j, _d2):
        return (jnp.where(obj_core[j], jnp.minimum(best, obj_labels[j]), best),
                jnp.bool_(False))

    if order is None:
        order = jnp.arange(n, dtype=jnp.int32)
    sel = queries_mask[order]
    active = jnp.sum(sel, dtype=jnp.int32)
    slot = jnp.where(sel, jnp.cumsum(sel, dtype=jnp.int32) - 1, padded)
    lanes = jnp.zeros((padded,), jnp.int32).at[slot].set(
        order.astype(jnp.int32), mode="drop").reshape(-1, c)
    live = (jnp.arange(padded, dtype=jnp.int32) < active).reshape(-1, c)

    def chunk(k, out):
        idx, on = lanes[k], live[k]
        m = query(bvh, within(query_pts[idx], eps_q), fn, sentinel,
                  start_nodes=jnp.where(on, 0, SENTINEL))
        return out.at[jnp.where(on, idx, n)].set(m, mode="drop")

    return jax.lax.fori_loop(0, traversed_lanes(sel) // c, chunk,
                             jnp.full((n,), sentinel, sentinel.dtype))


def _min_core_label_pass(bvh, points, eps, parent, core, queries_mask, n):
    """Self-join wrapper: queries == objects == ``points``, lanes in the
    tree's leaf order."""
    return min_core_label_on(bvh, points, eps, parent, core, queries_mask, n,
                             order=bvh.leaf_perm)


def _finish_labels(parent, border_candidate, core, n):
    with jax.named_scope("dbscan.finish"):
        labels = jnp.where(core, parent, jnp.where(border_candidate < n, border_candidate, NOISE))
        # Border candidates were captured against possibly-stale parents; chase.
        labels_safe = jnp.where(labels >= 0, labels, jnp.arange(n, dtype=jnp.int32))
        resolved = union_find.compress(jnp.where(core, parent, labels_safe).astype(jnp.int32))
        return jnp.where(labels >= 0, resolved, NOISE).astype(jnp.int32)


def union_rounds(bvh, points, eps, core, n, max_rounds=64):
    """Fixpoint: hook each core point's root under the min core-neighbor label,
    then pointer-jump. Labels converge to the min original index per cluster.

    Public so the distributed layer can run the same local union fixpoint on a
    per-shard tree before the cross-shard label rounds."""
    parent0 = jnp.arange(n, dtype=jnp.int32)

    def cond(state):
        _, changed, r = state
        return changed & (r < max_rounds)

    def body(state):
        parent, _, r = state
        m = _min_core_label_pass(bvh, points, eps, parent, core, core, n)
        m = jnp.where(core, m, n)
        # hook: parent[parent[i]] <- min(., m_i) for core i (scatter-min, det.)
        tgt = jnp.where(core, parent, n - 1)  # dummy target for non-core
        upd = jnp.where(core, jnp.minimum(m, parent), parent[tgt])
        parent2 = parent.at[tgt].min(upd)
        parent2 = union_find.compress(parent2)
        return parent2, jnp.any(parent2 != parent), r + 1

    with jax.named_scope("dbscan.union"):
        parent, _, rounds = jax.lax.while_loop(
            cond, body, (parent0, jnp.bool_(True), jnp.int32(0)))
    return parent, rounds


_union_rounds = union_rounds


@partial(jax.jit, static_argnames=("min_pts", "early_stop", "use_stack", "use_64bit"))
def fdbscan(points: jax.Array, eps, min_pts: int, *, early_stop: bool = True,
            use_stack: bool = False, use_64bit: bool = True) -> DbscanResult:
    """FDBSCAN (§4.3.3): fused traversal + count + union, O(n) memory."""
    n = points.shape[0]
    lo, hi = _scene(points)
    bvh = build_bvh(points, lo, hi, use_64bit=use_64bit)

    core = _core_mask(bvh, points, eps, min_pts, early_stop=early_stop, use_stack=use_stack)
    parent, rounds = _union_rounds(bvh, points, eps, core, n)
    with jax.named_scope("dbscan.border_pass"):
        border = _min_core_label_pass(bvh, points, eps, parent, core, ~core, n)
    labels = _finish_labels(parent, border, core, n)
    # float32: rounds x lanes would wrap int32 at tens of millions of points
    f32 = jnp.float32
    lanes = (rounds.astype(f32) * traversed_lanes(core).astype(f32)
             + traversed_lanes(~core).astype(f32))
    lane_share = lanes / ((rounds + 1).astype(f32) * f32(n))
    return DbscanResult(labels=labels, core_mask=core, num_rounds=rounds,
                        lane_share=lane_share)


# ---------------------------------------------------------------------------
# Initial implementation (§4.3.1): explicit adjacency graph + CC
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("min_pts", "neighbor_capacity", "use_64bit"))
def dbscan_graph_cc(points: jax.Array, eps, min_pts: int,
                    neighbor_capacity: int = 64, use_64bit: bool = True) -> DbscanResult:
    """The pre-callback baseline: store the ε-graph, then run CC.

    Reproduces the documented drawback — O(n·cap) memory, and the result is
    only correct when no neighborhood exceeds ``neighbor_capacity`` (the
    paper: "storing the found objects results in running out of memory").
    Kept for the Fig. 4 benchmark ladder.
    """
    n = points.shape[0]
    lo, hi = _scene(points)
    bvh = build_bvh(points, lo, hi, use_64bit=use_64bit)

    # The engine's fixed-capacity output protocol IS the documented
    # drawback: surplus neighbors overwrite the last slot.
    with jax.named_scope("dbscan.core_pass"):
        nbrs, counts, _overflow = query_fixed(
            bvh, within(points, jnp.asarray(eps, points.dtype)),
            capacity=neighbor_capacity)
        core = counts >= min_pts

    # Core-core edges from the stored graph.
    with jax.named_scope("dbscan.union"):
        src = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], nbrs.shape)
        valid = (nbrs >= 0) & core[src] & core[jnp.clip(nbrs, 0, n - 1)]
        parent = union_find.connected_components(n, src.ravel(), jnp.clip(nbrs, 0, n - 1).ravel(),
                                                 valid.ravel())
        parent = jnp.where(core, parent, jnp.arange(n, dtype=jnp.int32))

    # Border: min core-neighbor root from the stored graph.
    with jax.named_scope("dbscan.border_pass"):
        nbr_safe = jnp.clip(nbrs, 0, n - 1)
        cand = jnp.where((nbrs >= 0) & core[nbr_safe], parent[nbr_safe], n)
        border = jnp.min(cand, axis=1).astype(jnp.int32)
    labels = _finish_labels(parent, border, core, n)
    return DbscanResult(labels=labels, core_mask=core, num_rounds=jnp.int32(1),
                        lane_share=jnp.float32(1.0))


# ---------------------------------------------------------------------------
# FDBSCAN with pair traversal (§4.2.3, improvement (7))
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("min_pts", "edge_capacity", "use_64bit"))
def fdbscan_pair(points: jax.Array, eps, min_pts: int,
                 edge_capacity: int = 8, use_64bit: bool = True) -> DbscanResult:
    """FDBSCAN whose union phase visits each unordered pair once.

    Each core query i captures up to ``edge_capacity`` CROSS-ROOT core
    neighbors j > i (in Morton order) and stops early when the buffer fills —
    the callback-side analogue of ECL-CC skipping same-root unions. The outer
    loop repeats while any buffer overflowed or labels changed; every
    overflowing round performs ≥1 merging union, so progress is guaranteed.
    """
    n = points.shape[0]
    lo, hi = _scene(points)
    bvh = build_bvh(points, lo, hi, use_64bit=use_64bit)

    core = _core_mask(bvh, points, eps, min_pts, early_stop=True)

    def capture(parent):
        # Engine pair backend: callback sees each unordered ε-pair once,
        # already distance-gated; carries come back in sorted query order.
        def fn(carry, i_orig, j_orig, _d2):
            buf, cnt = carry
            take = core[i_orig] & core[j_orig] & (parent[i_orig] != parent[j_orig])
            slot = jnp.clip(cnt, 0, edge_capacity - 1)
            buf = jnp.where(take, buf.at[slot].set(j_orig), buf)
            cnt = cnt + take.astype(jnp.int32)
            return (buf, cnt), cnt >= edge_capacity

        buf0 = jnp.full((edge_capacity,), -1, jnp.int32)
        return query(bvh, within(points, jnp.asarray(eps, points.dtype)),
                     fn, (buf0, jnp.int32(0)), backend="pair")

    def cond(state):
        _, changed, overflow, r = state
        return (changed | overflow) & (r < 64)

    def body(state):
        parent, _, _, r = state
        buf, cnt = capture(parent)
        overflow = jnp.any(cnt >= edge_capacity)
        # Buffer row k belongs to SORTED query k; its original id is leaf_perm[k].
        src = jnp.broadcast_to(bvh.leaf_perm[:, None], buf.shape)
        mask = buf >= 0
        parent2 = union_find.hook_min(parent, src.ravel(),
                                      jnp.clip(buf, 0, n - 1).ravel(), mask.ravel())
        parent2 = union_find.compress(parent2)
        return parent2, jnp.any(parent2 != parent), overflow, r + 1

    parent0 = jnp.arange(n, dtype=jnp.int32)
    with jax.named_scope("dbscan.union"):
        parent, _, _, rounds = jax.lax.while_loop(
            cond, body, (parent0, jnp.bool_(True), jnp.bool_(True), jnp.int32(0)))
    parent = jnp.where(core, parent, jnp.arange(n, dtype=jnp.int32))

    with jax.named_scope("dbscan.border_pass"):
        border = _min_core_label_pass(bvh, points, eps, parent, core, ~core, n)
    labels = _finish_labels(parent, border, core, n)
    return DbscanResult(labels=labels, core_mask=core, num_rounds=rounds,
                        lane_share=jnp.float32(1.0))


# ---------------------------------------------------------------------------
# FDBSCAN-DenseBox (§4.3.4)
# ---------------------------------------------------------------------------

def _seg_min(values_sorted: jax.Array, run_start: jax.Array) -> jax.Array:
    """Per-run min of values over the grid's cell runs (values in sorted order):
    forward min-scan restarted at run heads, then backward broadcast."""
    n = values_sorted.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    is_head = idx == run_start

    def fwd(a, b):
        # b overwrites if b is a head, else combine.
        val_a, head_a = a
        val_b, head_b = b
        return jnp.where(head_b, val_b, jnp.minimum(val_a, val_b)), head_a | head_b

    mins, _ = jax.lax.associative_scan(fwd, (values_sorted, is_head))
    # mins[t] = min over [run_start..t]; the run's min is mins at the run END
    # (run_start + run_length - 1), gathered by seg_min_per_point.
    return mins


def seg_min_per_point(values_sorted, run_start, run_length):
    mins = _seg_min(values_sorted, run_start)
    return mins[run_start + run_length - 1]


@partial(jax.jit, static_argnames=("min_pts", "use_64bit"))
def fdbscan_densebox(points: jax.Array, eps, min_pts: int,
                     use_64bit: bool = True) -> DbscanResult:
    """FDBSCAN-DenseBox (§4.3.4): mixed BVH over dense cells + loose points."""
    import math

    n, d = points.shape
    lo, hi = _scene(points)
    eps_f = jnp.asarray(eps, points.dtype)
    eps2 = eps_f ** 2
    grid = build_cell_grid(points, lo, hi, eps_f / math.sqrt(d))

    dense_s = grid.dense_mask_sorted(min_pts)          # per sorted point
    head_s = grid.is_run_head()
    pts_sorted = points[grid.perm]

    # --- Mixed leaf set in grid-sorted order (n fixed leaves): -------------
    #   dense head      -> the cell's box           (active "cell" leaf)
    #   dense non-head  -> its own point            (inactive; callback skips)
    #   loose point     -> its own point
    cell_lo, cell_hi = cell_box(grid, grid.cell_coord_sorted)
    leaf_is_cell = dense_s & head_s
    skip_leaf = dense_s & ~head_s
    leaf_lo = jnp.where(leaf_is_cell[:, None], cell_lo, pts_sorted)
    leaf_hi = jnp.where(leaf_is_cell[:, None], cell_hi, pts_sorted)
    bvh = build_bvh_objects(leaf_lo, leaf_hi, lo, hi, use_64bit=use_64bit)

    max_run = 1 << 20  # static bound for the inner cell scan

    def cell_scan(center, start, length, init, step):
        """Bounded loop over a cell's sorted points: step(carry, t) applied for
        t in [start, start+length)."""
        def body(state):
            t, carry = state
            carry = step(carry, t)
            return t + 1, carry

        def cond(state):
            t, carry = state
            return t < start + length

        _, out = jax.lax.while_loop(cond, body, (start, init))
        return out

    # --- Phase 1: core classification. Dense-cell points are core for free. --
    # Engine callback over the mixed tree: the predicate gate tests the leaf
    # VOLUME (cell box or point), so cells outside ε are skipped wholesale.
    def count_cb(count, qi, t, _d2):
        # qi = grid-sorted query index, t = grid-sorted object index. The
        # center gather is loop-invariant in qi; XLA's LICM hoists it out
        # of the traversal loop (timed: no cost vs the old vmap closure).
        center = pts_sorted[qi]

        def on_cell(count):
            # Whole cell within eps? add run_length wholesale.
            far2 = jnp.sum(jnp.maximum(jnp.abs(center - (cell_lo[t] + cell_hi[t]) * 0.5)
                                       + grid.cell_size * 0.5, 0.0) ** 2)
            whole = far2 <= eps2

            def scan_cell(c):
                def step(cc, u):
                    hit = jnp.sum((pts_sorted[u] - center) ** 2) <= eps2
                    return cc + hit.astype(jnp.int32)
                return cell_scan(center, grid.run_start[t], grid.run_length[t], c, step)

            return jnp.where(whole, count + grid.run_length[t], scan_cell(count))

        def on_point(count):
            hit = jnp.sum((pts_sorted[t] - center) ** 2) <= eps2
            return count + hit.astype(jnp.int32)

        count = jnp.where(
            skip_leaf[t], count,
            jnp.where(leaf_is_cell[t], on_cell(count), on_point(count)))
        return count, count >= min_pts

    # Queries only for loose (non-dense-cell) points, in grid-sorted order.
    with jax.named_scope("dbscan.core_pass"):
        counts_s = query(bvh, within(pts_sorted, eps_f), count_cb, jnp.int32(0))
        counts_s = jnp.where(~dense_s, counts_s, jnp.int32(0))
        core_s = dense_s | (counts_s >= min_pts)
        core = jnp.zeros(n, bool).at[grid.perm].set(core_s)

    # --- Phase 2: union rounds. Pre-union dense cells to their min member. --
    seg_min_orig = seg_min_per_point(grid.perm, grid.run_start, grid.run_length)
    # Dense-cell points are pre-unioned to the min original index in their cell;
    # scatter-min with own index elsewhere keeps identity.
    parent0 = jnp.arange(n, dtype=jnp.int32).at[grid.perm].min(
        jnp.where(dense_s, seg_min_orig, grid.perm))

    def min_label_pass(parent, queries_mask_s):
        # Per-cell current min label (for wholesale cell hits).
        cell_lab = seg_min_per_point(parent[grid.perm], grid.run_start, grid.run_length)

        def cb(best, qi, t, _d2):
            center = pts_sorted[qi]

            def on_cell(best):
                far2 = jnp.sum((jnp.maximum(jnp.abs(center - (cell_lo[t] + cell_hi[t]) * 0.5), 0.0)
                                + grid.cell_size * 0.5) ** 2)
                whole = far2 <= eps2

                def scan_cell(b):
                    def step(bb, u):
                        hit = jnp.sum((pts_sorted[u] - center) ** 2) <= eps2
                        return jnp.where(hit, jnp.minimum(bb, parent[grid.perm[u]]), bb)
                    return cell_scan(center, grid.run_start[t], grid.run_length[t], b, step)

                return jnp.where(whole, jnp.minimum(best, cell_lab[t]), scan_cell(best))

            def on_point(best):
                j = grid.perm[t]
                hit = (jnp.sum((pts_sorted[t] - center) ** 2) <= eps2) & core[j]
                return jnp.where(hit, jnp.minimum(best, parent[j]), best)

            best = jnp.where(
                skip_leaf[t], best,
                jnp.where(leaf_is_cell[t], on_cell(best), on_point(best)))
            return best, jnp.bool_(False)

        m_s = query(bvh, within(pts_sorted, eps_f), cb, jnp.int32(n))
        m_s = jnp.where(queries_mask_s, m_s, jnp.int32(n))
        return jnp.full(n, n, jnp.int32).at[grid.perm].min(m_s)

    # Union queries run from EVERY core point. A head-only representative
    # per dense cell under-merges: the one-directional min-label hook relies
    # on the pair being seen from BOTH endpoints' queries, and a loose point
    # within ε of a non-head member (but not of the head) is only seen from
    # its own side — if its label is the smaller one, the cell never adopts
    # it (regression caught by the Fig-4 ladder cross-check at n=512).
    # DenseBox's savings are preserved where they matter: dense members skip
    # the COUNT phase entirely and are pre-unioned, intra-cell pair tests
    # never happen, and whole-cell hits are processed wholesale.
    union_queries_s = core_s

    def cond(state):
        _, changed, r = state
        return changed & (r < 64)

    def body(state):
        parent, _, r = state
        m = min_label_pass(parent, union_queries_s)
        m = jnp.where(core, m, n)
        tgt = jnp.where(core, parent, n - 1)
        upd = jnp.where(core, jnp.minimum(m, parent), parent[tgt])
        parent2 = parent.at[tgt].min(upd)
        parent2 = union_find.compress(parent2)
        return parent2, jnp.any(parent2 != parent), r + 1

    with jax.named_scope("dbscan.union"):
        parent, _, rounds = jax.lax.while_loop(
            cond, body, (union_find.compress(parent0), jnp.bool_(True), jnp.int32(0)))

    # --- Border pass for non-core points. ---
    with jax.named_scope("dbscan.border_pass"):
        border = min_label_pass(parent, ~core_s)  # scattered to original order
    labels = _finish_labels(parent, border, core, n)
    return DbscanResult(labels=labels, core_mask=core, num_rounds=rounds,
                        lane_share=jnp.float32(1.0))
