"""Pallas wavefront traversal: one batched BVH kernel under every query.

The engine's ``backend="pallas"``.  A grid step owns a *block* of
(ideally Morton-sorted) queries; the BVH node arrays (``rope`` /
``left_child`` / ``node_lo`` / ``node_hi`` / ``leaf_perm``) are staged
into the kernel as full-array blocks (VMEM on TPU), and each inner
``while_loop`` iteration advances every query in the block one rope hop
— the warp-style wavefront the source paper credits for its largest
wins (§4.1.1, §4.3.3), with the callback fused as the epilogue of the
leaf test exactly as in the vmapped cores.

Two entry points mirror the two traversal shapes the engine stages:

* :func:`wavefront_traverse` — the count/callback pass behind
  ``query``/``query_count`` (optionally carrying the ``TraversalStats``
  counters in the loop state when ``with_stats=True``);
* :func:`wavefront_fill_round` — one resumable chunk round of the
  ``query_csr_device`` scatter-fill protocol (per-lane node cursor in,
  ``(block, chunk)`` hit buffer out), driven by the engine's outer
  emit loop.

Closure discipline: a Pallas kernel body must not capture outer traced
arrays, so callers pass a ``make_fns(tree)`` *factory* instead of
prebuilt ``node_fn``/``leaf_fn`` closures.  The factory is re-invoked
inside the kernel on a :class:`TreeView` built from kernel-local ref
reads, giving closures whose captured arrays live in kernel memory.
Interpret mode only, for now: Mosaic's gather lowering refuses the
in-kernel per-lane table reads (``tree.rope[node]``,
``tree.node_lo[node]`` ... inside the ``while_loop``), so a native
compile raises :data:`LOWERING_GAP` instead of silently interpreting.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bvh import SENTINEL

from repro.kernels.ops import INTERPRET, pad_rows, pad_rows_edge, round_up

__all__ = ["BLOCK_Q", "LOWERING_GAP", "TreeView", "require_lowering",
           "wavefront_traverse", "wavefront_fill_round"]

# Default queries per grid step. 128 matches the TPU lane width; interpret
# mode accepts anything.
BLOCK_Q = 128

# Python-int twin of core.bvh.SENTINEL for use INSIDE kernel bodies: a
# Pallas kernel may not capture jnp array constants (SENTINEL is a
# jnp.int32 scalar).
_SENT = int(SENTINEL)

LOWERING_GAP = (
    "backend='pallas' has no native TPU lowering: Mosaic's gather lowering "
    "rule refuses the wavefront kernel's per-lane node-table reads "
    "(tree.rope[node], tree.left_child[node], tree.node_lo[node] inside its "
    "while_loop: 'Shape mismatch in input, indices and output'). Use "
    "backend='stackless' on a TPU (ROADMAP R1).")


def require_lowering(interpret: bool) -> None:
    """Raise :data:`LOWERING_GAP` unless the kernel is to be interpreted."""
    if not interpret:
        raise NotImplementedError(LOWERING_GAP)


class TreeView(NamedTuple):
    """Kernel-local view of the BVH arrays a rope traversal needs.

    Duck-types the subset of ``Bvh`` that ``core.query``'s predicate
    factories read (``node_lo``/``node_hi``/``leaf_perm``/``num_leaves``),
    so the same ``_pred_fns`` code builds closures against either the
    host-side tree or this in-kernel view.
    """

    leaf_perm: jax.Array
    left_child: jax.Array
    rope: jax.Array
    node_lo: jax.Array
    node_hi: jax.Array

    @property
    def num_leaves(self) -> int:
        return self.leaf_perm.shape[0]


def _tree_arrays(bvh) -> tuple:
    return (bvh.leaf_perm, bvh.left_child, bvh.rope, bvh.node_lo, bvh.node_hi)


def _full_spec(a: jax.Array) -> pl.BlockSpec:
    nd = a.ndim
    return pl.BlockSpec(a.shape, lambda i, _nd=nd: (0,) * _nd)


def _lane_spec(a: jax.Array, bq: int) -> pl.BlockSpec:
    nd = a.ndim
    return pl.BlockSpec((bq,) + a.shape[1:], lambda i, _nd=nd: (i,) + (0,) * (_nd - 1))


def _block_size(q: int, block_q: int) -> tuple[int, int]:
    bq = min(int(block_q), max(8, round_up(q, 8)))
    return bq, round_up(q, bq)


def _bcast(mask: jax.Array, ndim: int) -> jax.Array:
    return mask.reshape(mask.shape + (1,) * (ndim - 1))


def wavefront_traverse(bvh, qdata, make_fns: Callable, carry_init, *,
                       start_nodes: jax.Array | None = None,
                       with_stats: bool = False,
                       depths: jax.Array | None = None,
                       block_q: int = BLOCK_Q,
                       interpret: bool = INTERPRET):
    """Run the rope traversal for every query as a blocked wavefront.

    ``qdata`` is the engine's per-query pytree (leading dim = queries);
    ``make_fns(tree)`` must return ``(node_fn, leaf_fn)`` with the engine
    contracts (``node_fn(q, carry, node) -> bool``,
    ``leaf_fn(q, carry, obj, sorted_idx) -> (carry, done)``) built against
    the :class:`TreeView` it receives.  ``carry_init`` is broadcast to one
    carry per query.  ``start_nodes`` defaults to the root for every lane;
    padded lanes start at ``SENTINEL`` and never move.

    Returns the per-query carries, or with ``with_stats=True`` (which
    requires the node ``depths`` table) the tuple
    ``(carries, (nodes, aabb, leaf, maxd, done))`` matching the engine's
    ``_stats_from_raw`` layout.
    """
    require_lowering(interpret)
    leaves = jax.tree.leaves(qdata)
    if not leaves:
        raise ValueError("qdata must contain at least one per-query array")
    q = leaves[0].shape[0]
    if with_stats and depths is None:
        raise ValueError("with_stats=True requires the node depth table")
    if q == 0:
        carries = jax.tree.map(
            lambda x: jnp.broadcast_to(jnp.asarray(x), (0,) + jnp.shape(x)),
            carry_init)
        if not with_stats:
            return carries
        z = jnp.zeros((0,), jnp.int32)
        return carries, (z, z, z, z, jnp.zeros((0,), bool))

    bq, qp = _block_size(q, block_q)
    qdata_p = jax.tree.map(lambda x: pad_rows_edge(x, qp), qdata)
    if start_nodes is None:
        start = jnp.zeros((q,), jnp.int32)
    else:
        start = start_nodes.astype(jnp.int32)
    start = pad_rows(start, qp, SENTINEL)
    carries_p = jax.tree.map(
        lambda x: jnp.broadcast_to(jnp.asarray(x), (qp,) + jnp.shape(x)),
        carry_init)

    q_flat, q_def = jax.tree.flatten(qdata_p)
    c_flat, c_def = jax.tree.flatten(carries_p)
    n_q, n_c = len(q_flat), len(c_flat)

    tree_arrs = _tree_arrays(bvh)
    inputs: list = list(tree_arrs)
    in_specs = [_full_spec(a) for a in tree_arrs]
    if with_stats:
        inputs.append(depths)
        in_specs.append(_full_spec(depths))
    inputs.append(start)
    in_specs.append(_lane_spec(start, bq))
    inputs += q_flat
    in_specs += [_lane_spec(a, bq) for a in q_flat]
    inputs += c_flat
    in_specs += [_lane_spec(a, bq) for a in c_flat]

    out_shape = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in c_flat]
    out_specs = [_lane_spec(a, bq) for a in c_flat]
    if with_stats:
        for dt in (jnp.int32, jnp.int32, jnp.int32, jnp.int32, jnp.bool_):
            out_shape.append(jax.ShapeDtypeStruct((qp,), dt))
            out_specs.append(pl.BlockSpec((bq,), lambda i: (i,)))

    def kernel(*refs):
        it = iter(refs)
        tree = TreeView(*(next(it)[...] for _ in range(5)))
        depth_tab = next(it)[...] if with_stats else None
        node0 = next(it)[...]
        qblock = jax.tree.unflatten(q_def, [next(it)[...] for _ in range(n_q)])
        carry0 = jax.tree.unflatten(c_def, [next(it)[...] for _ in range(n_c)])
        out_refs = list(it)

        node_fn, leaf_fn = make_fns(tree)
        n = tree.num_leaves

        def cond(state):
            node, done = state[0], state[2]
            return jnp.any((node != _SENT) & ~done)

        def body(state):
            node, carry, done, nodes, aabb, leafs, maxd = state
            live = (node != _SENT) & ~done
            # Dead lanes sit at SENTINEL; clip every gather index so they
            # read node 0 harmlessly and are masked out below.
            node_s = jnp.clip(node, 0, 2 * n - 2)
            leaf_raw = node_s >= n - 1
            is_leaf = live & leaf_raw
            sorted_idx = node_s - (n - 1)
            objs = tree.leaf_perm[jnp.clip(sorted_idx, 0, n - 1)]

            carry_leaf, done_leaf = jax.vmap(leaf_fn)(
                qblock, carry, objs, sorted_idx)
            hit = jax.vmap(node_fn)(qblock, carry, node_s)
            node_c = jnp.clip(node_s, 0, n - 2)
            nxt = jnp.where(
                leaf_raw, tree.rope[node_s],
                jnp.where(hit, tree.left_child[node_c], tree.rope[node_s]))

            if with_stats:
                nodes = nodes + live.astype(jnp.int32)
                aabb = aabb + (live & ~leaf_raw).astype(jnp.int32)
                leafs = leafs + is_leaf.astype(jnp.int32)
                maxd = jnp.where(
                    live, jnp.maximum(maxd, depth_tab[node_s]), maxd)

            carry = jax.tree.map(
                lambda a, b: jnp.where(_bcast(is_leaf, a.ndim), a, b),
                carry_leaf, carry)
            done = done | (is_leaf & done_leaf)
            node = jnp.where(live, nxt, node)
            return node, carry, done, nodes, aabb, leafs, maxd

        z = jnp.zeros(node0.shape, jnp.int32)
        state0 = (node0, carry0, jnp.zeros(node0.shape, bool), z, z, z, z)
        _, carry, done, nodes, aabb, leafs, maxd = jax.lax.while_loop(
            cond, body, state0)

        outs = list(jax.tree.leaves(carry))
        if with_stats:
            outs += [nodes, aabb, leafs, maxd, done]
        for ref, val in zip(out_refs, outs):
            ref[...] = val

    outs = pl.pallas_call(
        kernel,
        grid=(qp // bq,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*inputs)
    if not isinstance(outs, (list, tuple)):
        outs = [outs]
    carry_out = jax.tree.unflatten(c_def, [o[:q] for o in outs[:n_c]])
    if not with_stats:
        return carry_out
    nodes, aabb, leafs, maxd, done = (o[:q] for o in outs[n_c:])
    return carry_out, (nodes, aabb, leafs, maxd, done)


def wavefront_fill_round(bvh, qdata, make_fns: Callable,
                         node_state: jax.Array, chunk: int, *,
                         block_q: int = BLOCK_Q,
                         interpret: bool = INTERPRET):
    """One chunk round of the resumable CSR scatter-fill, as a wavefront.

    ``make_fns(tree)`` must return ``(node_fn, leaf_aux)`` where
    ``leaf_aux(q, sorted_idx) -> (d2, hit)`` is the engine's predicate
    leaf test.  Each lane resumes from its ``node_state`` cursor, records
    up to ``chunk`` hit object ids into its buffer row, and parks either
    at ``SENTINEL`` (traversal finished) or at the node that would
    overflow the chunk (the engine's outer loop scatters the buffers and
    re-enters).  Mirrors the vmapped scalar ``round_one`` hop-for-hop.

    Returns ``(node_state, bufs, counts)`` with shapes
    ``(q,), (q, chunk), (q,)``.
    """
    require_lowering(interpret)
    q = node_state.shape[0]
    chunk = max(int(chunk), 1)
    if q == 0:
        return (node_state,
                jnp.full((0, chunk), -1, jnp.int32),
                jnp.zeros((0,), jnp.int32))

    bq, qp = _block_size(q, block_q)
    qdata_p = jax.tree.map(lambda x: pad_rows_edge(x, qp), qdata)
    state_p = pad_rows(node_state.astype(jnp.int32), qp, SENTINEL)
    q_flat, q_def = jax.tree.flatten(qdata_p)
    n_q = len(q_flat)

    tree_arrs = _tree_arrays(bvh)
    inputs = list(tree_arrs) + [state_p] + q_flat
    in_specs = ([_full_spec(a) for a in tree_arrs]
                + [_lane_spec(state_p, bq)]
                + [_lane_spec(a, bq) for a in q_flat])
    out_shape = [
        jax.ShapeDtypeStruct((qp,), jnp.int32),
        jax.ShapeDtypeStruct((qp, chunk), jnp.int32),
        jax.ShapeDtypeStruct((qp,), jnp.int32),
    ]
    out_specs = [
        pl.BlockSpec((bq,), lambda i: (i,)),
        pl.BlockSpec((bq, chunk), lambda i: (i, 0)),
        pl.BlockSpec((bq,), lambda i: (i,)),
    ]

    def kernel(*refs):
        it = iter(refs)
        tree = TreeView(*(next(it)[...] for _ in range(5)))
        node0 = next(it)[...]
        qblock = jax.tree.unflatten(q_def, [next(it)[...] for _ in range(n_q)])
        node_out, buf_out, nh_out = it

        node_fn, leaf_aux = make_fns(tree)
        n = tree.num_leaves

        def cond(state):
            node, _, nh = state
            return jnp.any((node != _SENT) & (nh < chunk))

        def body(state):
            node, buf, nh = state
            active = (node != _SENT) & (nh < chunk)
            node_s = jnp.clip(node, 0, 2 * n - 2)
            leaf_raw = node_s >= n - 1
            sorted_idx = jnp.clip(node_s - (n - 1), 0, n - 1)
            _, hit = jax.vmap(leaf_aux)(qblock, sorted_idx)
            take = active & leaf_raw & hit
            objs = tree.leaf_perm[sorted_idx]
            # One-hot write into each lane's next free slot.
            lane = jax.lax.broadcasted_iota(jnp.int32, (node.shape[0], chunk), 1)
            slot = jnp.clip(nh, 0, chunk - 1)
            write = take[:, None] & (lane == slot[:, None])
            buf = jnp.where(write, objs[:, None], buf)
            nh = nh + take.astype(jnp.int32)
            descend = jax.vmap(lambda qq, nd: node_fn(qq, None, nd))(
                qblock, node_s)
            node_c = jnp.clip(node_s, 0, n - 2)
            nxt = jnp.where(
                leaf_raw, tree.rope[node_s],
                jnp.where(descend, tree.left_child[node_c], tree.rope[node_s]))
            node = jnp.where(active, nxt, node)
            return node, buf, nh

        buf0 = jnp.full((node0.shape[0], chunk), -1, jnp.int32)
        nh0 = jnp.zeros(node0.shape, jnp.int32)
        node, buf, nh = jax.lax.while_loop(cond, body, (node0, buf0, nh0))
        node_out[...] = node
        buf_out[...] = buf
        nh_out[...] = nh

    node, buf, nh = pl.pallas_call(
        kernel,
        grid=(qp // bq,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*inputs)
    return node[:q], buf[:q], nh[:q]
