"""Pallas TPU kernels for ε-neighborhood primitives (DESIGN.md §2, TPU tier).

The paper's hot loop — BVH traversal with a fused callback (§4.1.1, §4.3.3) —
is a SIMT pointer-chase with no TPU analogue. The TPU-native reformulation
computes the same quantities as *tiled dense linear algebra* on the MXU:

    ‖xᵢ − yⱼ‖² = ‖xᵢ‖² + ‖yⱼ‖² − 2 xᵢ·yⱼ

with the −2xy term as a (TM, D) × (D, TN) matmul. The paper's callback is the
kernel *epilogue*, fused in VMEM (never materializing the (M, N) distance or
adjacency matrix — the O(n) memory property of FDBSCAN carries over):

* ``count`` epilogue   — |N_ε(x)| counting (core-point test, §4.1.2)
* ``minlabel`` epilogue — min cluster label over ε-reachable core neighbors
  (the UNION hook candidate, §4.2.3/§4.3.3)

Two kernel families:

* ``pairwise_*`` — all-pairs over row blocks of two point sets; grid
  (M/TM, N/TN) with accumulation over the N axis. Used for embedding-space
  clustering (in-situ analysis of d=64..4096 vectors) where the MXU
  contraction dimension is large.
* ``stencil_*`` — cosmology-style low-d points binned into ε-cells of fixed
  capacity C; grid (ncells, 3^d) where the candidate cell index comes from a
  scalar-prefetched neighbor map (SMEM), the TPU analogue of ArborX's
  cell-adjacency pruning (§4.3.4). Each (cell, stencil-slot) step is a
  (C, D) × (D, C) tile matmul.

Padding convention: padded points sit at ``BIG`` (1e15) so every distance to
them is ~1e30 ≫ ε²; padded labels are ``SENTINEL_LABEL`` (int32 max) and
padded core flags are False. All shapes are multiples of the block shapes —
``ops.py`` owns the padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 1e15  # padding coordinate; BIG**2 is finite in f32, so no NaNs
SENTINEL_LABEL = jnp.iinfo(jnp.int32).max

# Pallas kernels compile natively on a TPU and run in interpret mode on any
# other backend (same numerics; what the CPU test suite runs).
INTERPRET = jax.default_backend() != "tpu"

__all__ = [
    "BIG",
    "INTERPRET",
    "SENTINEL_LABEL",
    "pairwise_count",
    "pairwise_min_label",
    "stencil_count",
    "stencil_min_label",
]


def _dist2_tile(x, y):
    """(TM, D), (TN, D) -> (TM, TN) squared distances via the MXU."""
    xx = jnp.sum(x * x, axis=-1, keepdims=True)            # (TM, 1)
    yy = jnp.sum(y * y, axis=-1)[None, :]                  # (1, TN)
    xy = jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    return xx + yy - 2.0 * xy


# Every kernel below tiles distances CANDIDATES x QUERIES, so per-query
# results reduce over sublanes into a (1, TM) lane row: outputs are laid out
# (1, m) and per-candidate payloads (labels, core flags) as (n, 1) columns.
# No block is a 1-D int32 vector, whose HBM tiling Mosaic does not share.

# ---------------------------------------------------------------------------
# All-pairs kernels: grid (M/TM, N/TN), accumulate over axis 1
# ---------------------------------------------------------------------------

def _count_kernel(x_ref, y_ref, eps2_ref, o_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    d2 = _dist2_tile(y_ref[...], x_ref[...])               # (TN, TM)
    hits = (d2 <= eps2_ref[0]).astype(jnp.int32)
    o_ref[...] += jnp.sum(hits, axis=0, keepdims=True)


def _minlabel_kernel(x_ref, y_ref, lab_ref, core_ref, eps2_ref, o_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, SENTINEL_LABEL)

    d2 = _dist2_tile(y_ref[...], x_ref[...])               # (TN, TM)
    ok = (d2 <= eps2_ref[0]) & (core_ref[...] != 0)
    cand = jnp.where(ok, lab_ref[...], SENTINEL_LABEL)
    o_ref[...] = jnp.minimum(o_ref[...], jnp.min(cand, axis=0, keepdims=True))


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def pairwise_count(x: jax.Array, y: jax.Array, eps2: jax.Array,
                   *, tm: int = 128, tn: int = 128,
                   interpret: bool = INTERPRET) -> jax.Array:
    """counts[i] = |{j : ‖x_i − y_j‖² ≤ eps2}|. Shapes pre-padded to tiles."""
    m, d = x.shape
    n, _ = y.shape
    assert m % tm == 0 and n % tn == 0, (m, n, tm, tn)
    return pl.pallas_call(
        _count_kernel,
        grid=(m // tm, n // tn),
        in_specs=[
            pl.BlockSpec((tm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tn, d), lambda i, j: (j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, tm), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.int32),
        interpret=interpret,
    )(x, y, eps2.reshape(1))[0]


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def pairwise_min_label(x: jax.Array, y: jax.Array, labels: jax.Array,
                       core: jax.Array, eps2: jax.Array,
                       *, tm: int = 128, tn: int = 128,
                       interpret: bool = INTERPRET) -> jax.Array:
    """minlab[i] = min over ε-hits j with core[j] of labels[j] (else sentinel)."""
    m, d = x.shape
    n, _ = y.shape
    assert m % tm == 0 and n % tn == 0, (m, n, tm, tn)
    return pl.pallas_call(
        _minlabel_kernel,
        grid=(m // tm, n // tn),
        in_specs=[
            pl.BlockSpec((tm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((tn, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((tn, 1), lambda i, j: (j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, tm), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.int32),
        interpret=interpret,
    )(x, y, labels.reshape(n, 1), core.astype(jnp.int32).reshape(n, 1),
      eps2.reshape(1))[0]


# ---------------------------------------------------------------------------
# Stencil kernels: grid (ncells, n_stencil); candidate cell via scalar prefetch
# ---------------------------------------------------------------------------

def _stencil_count_kernel(nbr_ref, q_ref, c_ref, eps2_ref, o_ref):
    del nbr_ref  # consumed by the index maps
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    d2 = _dist2_tile(c_ref[0], q_ref[0])                   # (C, C)
    o_ref[0] += jnp.sum((d2 <= eps2_ref[0]).astype(jnp.int32), axis=0,
                        keepdims=True)


def _stencil_minlabel_kernel(nbr_ref, q_ref, c_ref, lab_ref, core_ref, eps2_ref, o_ref):
    del nbr_ref
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, SENTINEL_LABEL)

    d2 = _dist2_tile(c_ref[0], q_ref[0])                   # (C, C)
    ok = (d2 <= eps2_ref[0]) & (core_ref[0] != 0)
    cand = jnp.where(ok, lab_ref[0], SENTINEL_LABEL)
    o_ref[0] = jnp.minimum(o_ref[0], jnp.min(cand, axis=0, keepdims=True))


def _cell_spec(cap: int, d: int, s: int | None = None) -> pl.BlockSpec:
    """One cell's (1, cap, d) block: the grid's own cell, or (given the
    stencil size ``s``) its candidate read from the prefetched neighbor map,
    flattened to 1-D so SMEM holds it unpadded."""
    if s is None:
        return pl.BlockSpec((1, cap, d), lambda i, j, nbr: (i, 0, 0))
    return pl.BlockSpec((1, cap, d), lambda i, j, nbr: (nbr[i * s + j], 0, 0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def stencil_count(cell_pts: jax.Array, nbr_map: jax.Array, eps2: jax.Array,
                  *, interpret: bool = INTERPRET) -> jax.Array:
    """Per-slot ε-neighbor counts over the cell stencil.

    cell_pts: (ncells+1, C, D) — slot-padded cells; the LAST cell is all
              padding and is the target of out-of-bounds stencil entries.
    nbr_map:  (ncells, S) int32 — candidate cell id per (cell, stencil slot).
    Returns (ncells, C) int32 counts (garbage at padded slots).
    """
    ncells_p1, cap, d = cell_pts.shape
    ncells, s = nbr_map.shape
    assert ncells_p1 == ncells + 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ncells, s),
        in_specs=[
            _cell_spec(cap, d),
            _cell_spec(cap, d, s),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, cap), lambda i, j, nbr: (i, 0, 0)),
    )
    return pl.pallas_call(
        _stencil_count_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((ncells, 1, cap), jnp.int32),
        interpret=interpret,
    )(nbr_map.reshape(-1), cell_pts, cell_pts, eps2.reshape(1))[:, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def stencil_min_label(cell_pts: jax.Array, cell_labels: jax.Array,
                      cell_core: jax.Array, nbr_map: jax.Array, eps2: jax.Array,
                      *, interpret: bool = INTERPRET) -> jax.Array:
    """Per-slot min label over ε-reachable core points in the stencil.

    cell_labels: (ncells+1, C) int32 (sentinel at padding),
    cell_core:   (ncells+1, C) bool.
    Returns (ncells, C) int32.
    """
    ncells_p1, cap, d = cell_pts.shape
    ncells, s = nbr_map.shape
    assert ncells_p1 == ncells + 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ncells, s),
        in_specs=[
            _cell_spec(cap, d),
            _cell_spec(cap, d, s),
            _cell_spec(cap, 1, s),
            _cell_spec(cap, 1, s),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, cap), lambda i, j, nbr: (i, 0, 0)),
    )
    return pl.pallas_call(
        _stencil_minlabel_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((ncells, 1, cap), jnp.int32),
        interpret=interpret,
    )(nbr_map.reshape(-1), cell_pts, cell_pts, cell_labels[:, :, None],
      cell_core.astype(jnp.int32)[:, :, None], eps2.reshape(1))[:, 0]
