"""Grid FDBSCAN (DESIGN.md §2): ε-cell binning + MXU stencil kernels.

The faithful tier (``dbscan.py``) reproduces ArborX's SIMT algorithms; this
module is a tiled alternative for small boxes, NOT the production path: its
grid is dense, one cell per ε³ of the scene, so the paper's ε convention
needs about 4e8 cells at n = 2^21 (and the neighbor map must fit the
kernels' 1 MiB of SMEM). The halo-finding path is ``dbscan.fdbscan``. It
keeps the paper's insight —
spatially sort, test only geometrically adjacent candidates, fuse the
user operation into the traversal so neighbor lists are never materialized —
but expresses it as dense tile algebra:

  1. Bin points into a regular grid of ε-sized cells with a fixed per-cell
     capacity C (slot padding at BIG). The grid replaces the BVH: cell
     adjacency (a 3^d stencil) is the TPU analogue of BVH pruning.
  2. Core-point counting = ``stencil_count`` Pallas kernel: one (C, D)×(D, C)
     MXU tile per (cell, stencil slot), counting ε-hits in the epilogue
     (callback fusion, §4.1.1/§4.1.2).
  3. Cluster construction = iterated ``stencil_min_label`` + hook/compress
     (deterministic min-label union-find, §4.3.3 / deviation 3).
  4. Border points take the min ε-reachable core label (Ester semantics).

Everything after binning is fixed-shape and jit-compatible. Binning capacity
overflow is reported via an ``overflowed`` flag (the production driver
re-bins with a larger capacity — the same contract as ArborX's documented
out-of-memory behaviour for the adjacency-graph variant, §4.3.1, but
recoverable).
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.dbscan import NOISE, DbscanResult
from repro.core import union_find
from repro.kernels import ops as kops
from repro.kernels.pairwise import BIG, SENTINEL_LABEL

__all__ = ["CellBins", "GridAutoInfo", "bin_points", "stencil_neighbor_map",
           "fdbscan_grid", "fdbscan_grid_auto", "grid_dims_for"]


class CellBins(NamedTuple):
    """Slot-padded cell layout. ncells = prod(grid_dims); slot space is
    (ncells + 1, capacity) with the last cell all-padding (stencil sink)."""

    cell_pts: jax.Array        # (ncells + 1, C, D) float32, padded with BIG
    slot_of_point: jax.Array   # (n,) int32 flat slot id; overflow -> sink slot
    overflowed: jax.Array      # () bool — any point dropped by capacity

    @property
    def num_cells(self) -> int:  # static (shape-derived, jit-safe)
        return self.cell_pts.shape[0] - 1


def grid_dims_for(scene_lo, scene_hi, cell_size: float) -> tuple[int, ...]:
    """Static grid dims (host-side; scene box must be concrete)."""
    lo = np.asarray(scene_lo, np.float64)
    hi = np.asarray(scene_hi, np.float64)
    return tuple(int(max(1, math.ceil(e / cell_size))) for e in (hi - lo))


def stencil_neighbor_map(grid_dims: tuple[int, ...], reach: int = 1) -> np.ndarray:
    """(ncells, (2*reach+1)^d) int32 candidate-cell map; ncells = sink id for
    out-of-range neighbors. Host-side static table (scalar-prefetched)."""
    dims = np.asarray(grid_dims, np.int64)
    ncells = int(np.prod(dims))
    coords = np.stack(np.unravel_index(np.arange(ncells), grid_dims), axis=1)
    offs = np.stack(np.meshgrid(*([np.arange(-reach, reach + 1)] * len(grid_dims)),
                                indexing="ij"), axis=-1).reshape(-1, len(grid_dims))
    nb = coords[:, None, :] + offs[None, :, :]
    ok = ((nb >= 0) & (nb < dims[None, None, :])).all(-1)
    nb = np.clip(nb, 0, dims - 1)
    lin = np.ravel_multi_index(nb.reshape(-1, len(grid_dims)).T, grid_dims).reshape(nb.shape[:2])
    return np.where(ok, lin, ncells).astype(np.int32)


@partial(jax.jit, static_argnames=("grid_dims", "capacity"))
def bin_points(points: jax.Array, scene_lo: jax.Array, cell_size,
               grid_dims: tuple[int, ...], capacity: int) -> CellBins:
    n, d = points.shape
    dims = jnp.asarray(grid_dims, jnp.int32)
    ncells = int(np.prod(grid_dims))
    coord = jnp.floor((points - scene_lo) / cell_size).astype(jnp.int32)
    coord = jnp.clip(coord, 0, dims - 1)
    lin = coord[:, 0]
    for k in range(1, d):
        lin = lin * dims[k] + coord[:, k]

    # Rank within cell: stable sort by cell, rank = pos - run_start.
    order = jnp.argsort(lin, stable=True).astype(jnp.int32)
    lin_sorted = lin[order]
    idx = jnp.arange(n, dtype=jnp.int32)
    is_head = jnp.concatenate([jnp.ones(1, bool), lin_sorted[1:] != lin_sorted[:-1]])
    run_start = jax.lax.associative_scan(jnp.maximum, jnp.where(is_head, idx, 0))
    rank_sorted = idx - run_start

    ok_sorted = rank_sorted < capacity
    sink = ncells * capacity
    slot_sorted = jnp.where(ok_sorted, lin_sorted * capacity + rank_sorted, sink)
    slot = jnp.zeros(n, jnp.int32).at[order].set(slot_sorted)

    flat = jnp.full(((ncells + 1) * capacity, d), BIG, jnp.float32)
    flat = flat.at[slot].set(points.astype(jnp.float32), mode="drop")
    # Overflow points must NOT land in the sink cell as real coordinates.
    flat = flat.at[sink].set(jnp.full((d,), BIG, jnp.float32))

    return CellBins(
        cell_pts=flat.reshape(ncells + 1, capacity, d),
        slot_of_point=slot,
        overflowed=jnp.any(~ok_sorted),
    )


def _scatter_slots(values: jax.Array, fill, bins: CellBins, dtype=jnp.int32) -> jax.Array:
    """Scatter per-point values into the (ncells+1, C) slot layout."""
    ncells_p1, cap = bins.cell_pts.shape[:2]
    flat = jnp.full((ncells_p1 * cap,), fill, dtype)
    flat = flat.at[bins.slot_of_point].set(values.astype(dtype))
    sink = bins.num_cells * cap
    flat = flat.at[sink:].set(fill)  # overflow writes land in the sink; reset
    return flat.reshape(ncells_p1, cap)


@partial(jax.jit, static_argnames=("min_pts", "grid_dims", "capacity", "interpret", "max_rounds"))
def fdbscan_grid(points: jax.Array, eps, min_pts: int, *,
                 scene_lo, grid_dims: tuple[int, ...], capacity: int,
                 interpret: bool = kops.INTERPRET,
                 max_rounds: int = 64) -> tuple[DbscanResult, jax.Array]:
    """TPU-native FDBSCAN over (n, d) points. ``grid_dims`` must tile the
    scene with cells of size >= eps (use ``grid_dims_for(lo, hi, eps)``).

    Returns (DbscanResult, overflowed). Labels follow the same contract as
    the faithful tier: cluster root = min original index, noise = -1.
    """
    n, d = points.shape
    eps_f = jnp.asarray(eps, jnp.float32)
    bins = bin_points(points, jnp.asarray(scene_lo, jnp.float32), eps_f,
                      grid_dims, capacity)
    nbr_map = jnp.asarray(stencil_neighbor_map(grid_dims))
    ncells, cap = bins.num_cells, capacity

    # --- Phase 1: core classification (fused counting kernel). -------------
    counts_cells = kops.cell_stencil_counts(bins.cell_pts, nbr_map, eps_f,
                                            interpret=interpret)
    counts_flat = jnp.concatenate(
        [counts_cells.reshape(-1), jnp.zeros((cap,), jnp.int32)])
    counts = counts_flat[bins.slot_of_point]
    core = counts >= min_pts

    core_slots = _scatter_slots(core, False, bins, dtype=jnp.bool_)

    # --- Phase 2: union fixpoint (min-label kernel + hook/compress). -------
    parent0 = jnp.arange(n, dtype=jnp.int32)

    def min_label_pass(parent):
        lab_slots = _scatter_slots(jnp.where(core, parent, SENTINEL_LABEL),
                                   SENTINEL_LABEL, bins)
        m_cells = kops.cell_stencil_min_label(bins.cell_pts, lab_slots,
                                              core_slots, nbr_map, eps_f,
                                              interpret=interpret)
        m_flat = jnp.concatenate(
            [m_cells.reshape(-1), jnp.full((cap,), SENTINEL_LABEL, jnp.int32)])
        return m_flat[bins.slot_of_point]

    def cond(state):
        _, changed, r = state
        return changed & (r < max_rounds)

    def body(state):
        parent, _, r = state
        m = min_label_pass(parent)
        m = jnp.where(core & (m != SENTINEL_LABEL), m, parent)
        tgt = jnp.where(core, parent, n - 1)
        upd = jnp.where(core, jnp.minimum(m, parent), parent[tgt])
        parent2 = parent.at[tgt].min(upd)
        parent2 = union_find.compress(parent2)
        return parent2, jnp.any(parent2 != parent), r + 1

    parent, _, rounds = jax.lax.while_loop(cond, body, (parent0, jnp.bool_(True), jnp.int32(0)))

    # --- Border assignment: min core-neighbor root. -------------------------
    cand = min_label_pass(parent)
    border_ok = ~core & (cand != SENTINEL_LABEL)
    cand_safe = jnp.where(cand == SENTINEL_LABEL, 0, cand)
    resolved = union_find.compress(jnp.where(core, parent, jnp.where(border_ok, cand_safe, parent0)))
    labels = jnp.where(core | border_ok, resolved, NOISE).astype(jnp.int32)

    return (DbscanResult(labels=labels, core_mask=core, num_rounds=rounds,
                         lane_share=jnp.float32(1.0)), bins.overflowed)


class GridAutoInfo(NamedTuple):
    """Retry observability for ``fdbscan_grid_auto`` (mirrors the engine's
    ``BufferedCsr`` contract: never fail silently on capacity tuning)."""
    attempts: int   # passes taken (1 = zero-retry fast path)
    capacity: int   # cell capacity the successful attempt used
    overflowed: bool  # whether ANY attempt overflowed (i.e. retries happened)


def fdbscan_grid_auto(points: jax.Array, eps, min_pts: int, *, scene_lo,
                      scene_hi, capacity: int = 64, max_doublings: int = 6,
                      with_info: bool = False, **kw):
    """Auto-tuning driver (the paper's §5 future-work item, adapted): run
    the TPU-native FDBSCAN and, on capacity overflow, re-bin with doubled
    cell capacity — the recoverable analogue of the adjacency-graph
    variant's documented out-of-memory failure (§4.3.1). Host-side retry
    loop; each attempt is a fresh jit specialization.

    With ``with_info=True`` returns (DbscanResult, GridAutoInfo) so callers
    can see how many re-bins the capacity heuristic cost."""
    dims = grid_dims_for(scene_lo, scene_hi, float(eps))
    cap = capacity
    for attempt in range(1, max_doublings + 2):
        res, overflowed = fdbscan_grid(points, eps, min_pts, scene_lo=scene_lo,
                                       grid_dims=dims, capacity=cap, **kw)
        if not bool(overflowed):
            if with_info:
                return res, GridAutoInfo(attempts=attempt, capacity=cap,
                                         overflowed=attempt > 1)
            return res
        cap *= 2
    raise RuntimeError(
        f"fdbscan_grid_auto: capacity {cap // 2} still overflows after "
        f"{max_doublings} doublings (n={points.shape[0]}, dims={dims})")
