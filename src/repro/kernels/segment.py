"""Pallas TPU segmented reductions over SORTED segments (halo catalogs).

The halo-catalog hot loop (labels -> per-halo sums) is a segmented reduction:
``out[s] = reduce(data[i] for i where seg_ids[i] == s)``. XLA lowers
``.at[seg].add`` to a serial scatter on TPU; here the bulk of the work is
reformulated as *tiled one-hot matmuls* on the MXU (the same trick that made
the ε-neighborhood kernels in ``pairwise.py`` TPU-native):

1. rows are processed in tiles of ``T`` sorted rows, eight tiles per grid
   step (ids laid out ``(rows, T)``, features transposed ``(D, rows)``);
2. each tile builds a (T, 2T) one-hot matrix of its rows' segment ids
   RELATIVE to the tile's T-aligned base segment, and contracts the (D, T)
   data tile against it on the MXU -> a (D, 2T) aligned partial;
3. partials land in T-aligned windows of the output, so the final combine is
   a scatter-add of ``n/T`` contiguous (D, T) slabs — O(n/T) scatter updates
   instead of O(n).

Correctness requires the contract the catalog layer guarantees by
construction: ``seg_ids`` is sorted ascending AND dense (every id in
``[min_id, max_id]`` occurs at least once). Then a tile of T sorted rows
spans at most T consecutive ids, so every row's id fits in the 2T-wide
window anchored at ``(seg_ids[tile_start] // T) * T`` (the run of any id
strictly inside the tile's id range lies entirely inside the tile).

Two reductions, mirroring the catalog's needs:

* ``segment_sum_sorted`` — MXU one-hot matmul accumulation (counts, centers
  of mass, mean velocities, Σ|v|²);
* ``segment_max_sorted`` — same tiling with a VPU masked-max epilogue
  (per-halo max radius).

Pure-jnp oracles with identical contracts live in ``kernels/ref.py``
(``segment_sum_sorted_ref`` / ``segment_max_sorted_ref``). Padding: row
padding appended by the wrappers reuses the last real segment id with
neutral data (0 for sum, ``-SEG_NEG_BIG`` for max), so it never perturbs
real segments.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ops import INTERPRET, round_up

SEG_NEG_BIG = 1e30  # neutral element magnitude for the max reduction

__all__ = ["SEG_NEG_BIG", "segment_sum_sorted", "segment_max_sorted"]


# Sub-tiles per grid step: the (rows, T) int32 id block then matches the
# TPU's (8, 128) tiling. Features travel transposed, (D, rows), so no
# array pads an 8-wide minor dim out to 128 lanes.
_SUB = 8


def _tile_onehot(seg_ref, r, t):
    """Sub-tile ``r``'s (T, 2T) one-hot of its sorted ids against the
    T-aligned window anchored at its first id (in [0, 2T) by contract)."""
    row = seg_ref[r:r + 1, :]
    local = (row - (row[:, :1] // t) * t).T                    # (T, 1)
    return jax.lax.broadcasted_iota(jnp.int32, (t, 2 * t), 1) == local


def _sum_kernel(seg_ref, xt_ref, o_ref):
    """Each sub-tile of T rows -> one (D, 2T) aligned partial via a one-hot
    matmul on the MXU."""
    t = seg_ref.shape[1]
    for r in range(seg_ref.shape[0]):
        onehot = _tile_onehot(seg_ref, r, t).astype(jnp.float32)
        o_ref[r] = jax.lax.dot(xt_ref[:, r * t:(r + 1) * t], onehot,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _max_kernel(seg_ref, xt_ref, o_ref):
    """Same tiling with a VPU masked-max epilogue, one feature row at a
    time."""
    t = seg_ref.shape[1]
    for r in range(seg_ref.shape[0]):
        hit = _tile_onehot(seg_ref, r, t)                      # (T, 2T)
        for k in range(xt_ref.shape[0]):
            col = xt_ref[k:k + 1, r * t:(r + 1) * t].T          # (T, 1)
            o_ref[r, k:k + 1, :] = jnp.max(
                jnp.where(hit, col, -SEG_NEG_BIG), axis=0, keepdims=True)


def _partials(kernel, data, seg_ids, num_segments, tile, pad_value,
              interpret):
    """Pad rows to whole grid steps and features to 8, lay ids out as
    (rows, T), and run ``kernel`` -> (num_tiles, D, 2T) partials plus each
    tile's aligned block index."""
    n, d = data.shape
    npad = round_up(max(n, 1), _SUB * tile)
    dp = round_up(max(d, 1), 8)
    xt = jnp.pad(data.astype(jnp.float32).T, ((0, dp - d), (0, npad - n)),
                 constant_values=pad_value)
    seg = jnp.clip(seg_ids.astype(jnp.int32), 0, num_segments - 1)
    # Row padding reuses the LAST real id: stays sorted, window math holds.
    seg = jnp.pad(seg, (0, npad - n), mode="edge" if n > 0 else "constant")
    seg = seg.reshape(npad // tile, tile)
    num_tiles = seg.shape[0]
    partials = pl.pallas_call(
        kernel,
        grid=(num_tiles // _SUB,),
        in_specs=[pl.BlockSpec((_SUB, tile), lambda i: (i, 0)),
                  pl.BlockSpec((dp, _SUB * tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((_SUB, dp, 2 * tile), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_tiles, dp, 2 * tile), jnp.float32),
        interpret=interpret,
    )(seg, xt)
    return partials, seg[:, 0] // tile


def _combine(partials, blk, num_segments, tile, d, init, combine_at):
    """Scatter the T-aligned (D, 2T) partials into the (S, D) output:
    n/T slab updates instead of n row updates."""
    num_blocks = num_segments // tile + 2  # blk+1 always in range
    dp = partials.shape[1]
    out = jnp.full((num_blocks, dp, tile), init, jnp.float32)
    out = combine_at(out, blk, partials[:, :, :tile])
    out = combine_at(out, blk + 1, partials[:, :, tile:])
    out = out.transpose(0, 2, 1).reshape(num_blocks * tile, dp)
    return out[:num_segments, :d]


@functools.partial(jax.jit, static_argnames=("num_segments", "tile", "interpret"))
def segment_sum_sorted(data: jax.Array, seg_ids: jax.Array, num_segments: int,
                       *, tile: int = 128,
                       interpret: bool = INTERPRET) -> jax.Array:
    """out[s, :] = Σ data[i, :] over i with seg_ids[i] == s.

    ``seg_ids`` must be sorted ascending and dense (see module docstring);
    rows the caller wants excluded must be zeroed, not re-labeled.
    """
    partials, blk = _partials(_sum_kernel, data, seg_ids, num_segments, tile,
                              0.0, interpret)
    return _combine(partials, blk, num_segments, tile, data.shape[1], 0.0,
                    lambda o, idx, upd: o.at[idx].add(upd))


@functools.partial(jax.jit, static_argnames=("num_segments", "tile", "interpret"))
def segment_max_sorted(data: jax.Array, seg_ids: jax.Array, num_segments: int,
                       *, tile: int = 128,
                       interpret: bool = INTERPRET) -> jax.Array:
    """out[s, :] = max data[i, :] over i with seg_ids[i] == s; empty segments
    come back at ``-SEG_NEG_BIG`` (callers mask on their own count).

    Same sorted+dense contract as ``segment_sum_sorted``; rows to exclude
    must be set to ``-SEG_NEG_BIG`` by the caller.
    """
    partials, blk = _partials(_max_kernel, data, seg_ids, num_segments, tile,
                              -SEG_NEG_BIG, interpret)
    return _combine(partials, blk, num_segments, tile, data.shape[1],
                    -SEG_NEG_BIG, lambda o, idx, upd: o.at[idx].max(upd))
