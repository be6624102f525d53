"""Public jit'd wrappers around the Pallas kernels: padding, tiling, unpadding.

On a real TPU the kernels compile natively (``interpret=False``); on CPU they
run the kernel body in interpret mode — same numerics, used by every test.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import pairwise as _k
from repro.kernels.pairwise import INTERPRET

__all__ = [
    "eps_neighbor_counts",
    "eps_min_label",
    "cell_stencil_counts",
    "cell_stencil_min_label",
    "round_up",
    "pad_rows",
    "pad_rows_edge",
]


def round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def pad_rows(a: jax.Array, rows: int, fill) -> jax.Array:
    """Pad the leading dim of ``a`` to ``rows`` with ``fill``."""
    pad = rows - a.shape[0]
    if pad == 0:
        return a
    return jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1), constant_values=fill)


def pad_rows_edge(a: jax.Array, rows: int) -> jax.Array:
    """Pad the leading dim of ``a`` to ``rows`` by replicating the last row.

    Used by the wavefront kernel for per-query payloads: replicated rows carry
    valid geometry so the kernel math never sees NaN/garbage, while the lane
    itself is killed by a SENTINEL start node.
    """
    pad = rows - a.shape[0]
    if pad == 0:
        return a
    return jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1), mode="edge")


# Backward-compatible private aliases (pre-wavefront internal names).
_round_up = round_up
_pad_rows = pad_rows


def _pad_dim(a: jax.Array, d: int) -> jax.Array:
    pad = d - a.shape[1]
    if pad == 0:
        return a
    # Zero-pad feature dim: contributes 0 to distances for real rows; padded
    # rows already live at BIG in the padded dims that exist.
    return jnp.pad(a, [(0, 0), (0, pad)])


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def eps_neighbor_counts(x: jax.Array, y: jax.Array, eps,
                        *, tm: int = 128, tn: int = 128,
                        interpret: bool = INTERPRET) -> jax.Array:
    """|N_ε(x_i)| against point set y. Arbitrary (m, d), (n, d) float32."""
    m, d = x.shape
    n = y.shape[0]
    dp = _round_up(max(d, 1), 8)
    xp = _pad_dim(_pad_rows(x.astype(jnp.float32), _round_up(m, tm), _k.BIG), dp)
    yp = _pad_dim(_pad_rows(y.astype(jnp.float32), _round_up(n, tn), _k.BIG), dp)
    eps2 = jnp.asarray(eps, jnp.float32) ** 2
    out = _k.pairwise_count(xp, yp, eps2, tm=tm, tn=tn, interpret=interpret)
    return out[:m]


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def eps_min_label(x: jax.Array, y: jax.Array, labels: jax.Array, core: jax.Array,
                  eps, *, tm: int = 128, tn: int = 128,
                  interpret: bool = INTERPRET) -> jax.Array:
    """min label over ε-reachable core y-points; SENTINEL_LABEL when none."""
    m, d = x.shape
    n = y.shape[0]
    dp = _round_up(max(d, 1), 8)
    xp = _pad_dim(_pad_rows(x.astype(jnp.float32), _round_up(m, tm), _k.BIG), dp)
    yp = _pad_dim(_pad_rows(y.astype(jnp.float32), _round_up(n, tn), _k.BIG), dp)
    lp = _pad_rows(labels.astype(jnp.int32), _round_up(n, tn), _k.SENTINEL_LABEL)
    cp = _pad_rows(core.astype(bool), _round_up(n, tn), False)
    eps2 = jnp.asarray(eps, jnp.float32) ** 2
    out = _k.pairwise_min_label(xp, yp, lp, cp, eps2, tm=tm, tn=tn, interpret=interpret)
    return out[:m]


@functools.partial(jax.jit, static_argnames=("interpret",))
def cell_stencil_counts(cell_pts: jax.Array, nbr_map: jax.Array, eps,
                        *, interpret: bool = INTERPRET) -> jax.Array:
    """(ncells+1, C, D) slot-padded cells -> (ncells, C) ε-counts."""
    eps2 = jnp.asarray(eps, jnp.float32) ** 2
    return _k.stencil_count(cell_pts, nbr_map, eps2, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cell_stencil_min_label(cell_pts: jax.Array, cell_labels: jax.Array,
                           cell_core: jax.Array, nbr_map: jax.Array, eps,
                           *, interpret: bool = INTERPRET) -> jax.Array:
    eps2 = jnp.asarray(eps, jnp.float32) ** 2
    return _k.stencil_min_label(cell_pts, cell_labels, cell_core, nbr_map, eps2,
                                interpret=interpret)
