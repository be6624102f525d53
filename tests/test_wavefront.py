"""Kernel-specific tests for the Pallas wavefront traversal backend.

The backend-equivalence property tests in ``test_query.py`` already pin
``backend="pallas"`` against the numpy oracle on the adversarial
datasets; this file covers the shapes only the kernel layer can get
wrong — block padding (query counts that are not a multiple of the
block), dead-lane masking, the resumable chunk protocol at chunk=1, the
stats carry, and the direct ``wavefront_traverse`` contract.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.bvh import build_bvh
from repro.core.query import (
    query_count,
    query_csr,
    query_csr_device,
    traverse,
    within,
)
from repro.kernels.wavefront import wavefront_traverse


def _bvh(pts):
    pts = np.asarray(pts, np.float32)
    lo = pts.min(0) - 1e-4
    hi = pts.max(0) + 1e-4
    return build_bvh(jnp.asarray(pts), jnp.asarray(lo), jnp.asarray(hi))


def _counts_oracle(pts, centers, eps):
    d2 = ((centers[:, None] - pts[None]) ** 2).sum(-1, dtype=np.float32)
    return (d2 <= np.float32(eps) ** 2).sum(1)


# --- block-shape edges -------------------------------------------------------

@pytest.mark.parametrize("q", [1, 5, 8, 127, 128, 130])
def test_query_counts_at_block_boundaries(q):
    """Query counts straddling the 128-lane block: 1 (single live lane),
    127/128/130 (one short, exact, one over — two grid steps with 126
    dead lanes). Padded lanes must never contribute."""
    rng = np.random.default_rng(q)
    pts = rng.uniform(0, 1, (60, 3)).astype(np.float32)
    bvh = _bvh(pts)
    centers = rng.uniform(0, 1, (q, 3)).astype(np.float32)
    got = np.asarray(query_count(bvh, within(jnp.asarray(centers), 0.3),
                                 backend="pallas"))
    np.testing.assert_array_equal(got, _counts_oracle(pts, centers, 0.3))


def test_minimal_tree_n2():
    """The smallest tree (one internal node, two leaves)."""
    pts = np.float32([[0.1, 0.1, 0.1], [0.9, 0.9, 0.9]])
    bvh = _bvh(pts)
    centers = np.float32([[0.1, 0.1, 0.1], [0.5, 0.5, 0.5], [2.0, 2.0, 2.0]])
    got = np.asarray(query_count(bvh, within(jnp.asarray(centers), 0.05),
                                 backend="pallas"))
    np.testing.assert_array_equal(got, [1, 0, 0])


def test_degenerate_single_leaf_geometry():
    """All points coincident — every leaf AABB is the same point, Morton
    codes fully tie. The wavefront must still count all duplicates."""
    pts = np.full((16, 3), 0.5, np.float32)
    bvh = _bvh(pts)
    centers = np.float32([[0.5, 0.5, 0.5], [0.4, 0.4, 0.4]])
    got = np.asarray(query_count(bvh, within(jnp.asarray(centers), 0.0),
                                 backend="pallas"))
    np.testing.assert_array_equal(got, [16, 0])


def test_empty_query_set():
    """q=0 short-circuits before the kernel launch; every protocol shape
    stays consistent."""
    bvh = _bvh(np.random.default_rng(0).uniform(0, 1, (32, 3)))
    pred = within(jnp.zeros((0, 3), jnp.float32), 0.1)
    assert query_count(bvh, pred, backend="pallas").shape == (0,)
    res = query_csr(bvh, pred, backend="pallas")
    assert res.indices.shape == (0,) and res.offsets.shape == (1,)


# --- engine-contract parity against the stackless reference ------------------

def test_with_stats_matches_stackless_per_query():
    """The in-kernel stats carry must reproduce the instrumented scalar
    core column-for-column (same unsorted query order => same rows)."""
    rng = np.random.default_rng(7)
    pts = rng.uniform(0, 1, (90, 3)).astype(np.float32)
    bvh = _bvh(pts)
    pred = within(jnp.asarray(rng.uniform(0, 1, (41, 3)).astype(np.float32)), 0.25)
    _, s_ref = query_count(bvh, pred, backend="stackless", with_stats=True)
    _, s_pal = query_count(bvh, pred, backend="pallas", with_stats=True)
    for field in s_ref._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(s_ref, field)),
            np.asarray(getattr(s_pal, field)), err_msg=field)


def test_start_nodes_matches_stackless():
    """Pair-style subtree starts (rope of each leaf) must traverse the
    identical pruned frontier on both backends."""
    rng = np.random.default_rng(3)
    pts = rng.uniform(0, 1, (50, 3)).astype(np.float32)
    bvh = _bvh(pts)
    n = bvh.num_leaves
    starts = bvh.rope[jnp.arange(n, dtype=jnp.int32) + (n - 1)]
    pred = within(jnp.asarray(pts)[bvh.leaf_perm], 0.3)
    a = query_count(bvh, pred, backend="stackless", start_nodes=starts)
    b = query_count(bvh, pred, backend="pallas", start_nodes=starts)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_csr_device_chunk1_forces_resume_rounds():
    """chunk=1 maximizes resumable rounds — every hit pauses the lane; the
    scatter-fill must still produce the exact stackless CSR."""
    rng = np.random.default_rng(5)
    pts = (rng.uniform(0, 0.05, (40, 3)) + 0.5).astype(np.float32)
    bvh = _bvh(pts)
    pred = within(jnp.asarray(pts), 0.2)
    cap = 40 * 40 + 4
    ref = query_csr_device(bvh, pred, capacity=cap, chunk=1, backend="stackless")
    got = query_csr_device(bvh, pred, capacity=cap, chunk=1, backend="pallas")
    np.testing.assert_array_equal(np.asarray(ref.offsets), np.asarray(got.offsets))
    np.testing.assert_array_equal(np.asarray(ref.indices), np.asarray(got.indices))
    assert not bool(got.overflowed)


def test_stop_at_early_exit_parity():
    pts = np.full((32, 3), 0.25, np.float32)
    bvh = _bvh(pts)
    pred = within(jnp.full((6, 3), 0.25, jnp.float32), 0.1)
    a = query_count(bvh, pred, stop_at=4, backend="stackless")
    b = query_count(bvh, pred, stop_at=4, backend="pallas")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(b), 4)


# --- direct kernel contract --------------------------------------------------

def test_wavefront_traverse_direct_small_blocks():
    """Drive the kernel directly with block_q=8 so a 13-query workload
    spans two grid steps with 3 dead lanes, using a custom counting
    callback built by the factory."""
    rng = np.random.default_rng(9)
    pts = rng.uniform(0, 1, (30, 3)).astype(np.float32)
    bvh = _bvh(pts)
    centers = rng.uniform(0, 1, (13, 3)).astype(np.float32)
    eps2 = np.float32(0.3) ** 2
    qdata = (jnp.arange(13, dtype=jnp.int32), jnp.asarray(centers),
             jnp.full((13,), eps2, jnp.float32))

    def make_fns(tree):
        from repro.core.geometry import point_aabb_dist2
        n = tree.num_leaves

        def node_fn(q, carry, node):
            (_, center, r2) = q
            return point_aabb_dist2(center, tree.node_lo[node],
                                    tree.node_hi[node]) <= r2

        def leaf_fn(q, carry, obj, sorted_idx):
            (_, center, r2) = q
            leaf_node = jnp.clip(sorted_idx, 0, n - 1) + (n - 1)
            d2 = point_aabb_dist2(center, tree.node_lo[leaf_node],
                                  tree.node_hi[leaf_node])
            return carry + (d2 <= r2).astype(jnp.int32), jnp.bool_(False)

        return node_fn, leaf_fn

    got = wavefront_traverse(bvh, qdata, make_fns, jnp.int32(0), block_q=8)
    np.testing.assert_array_equal(np.asarray(got),
                                  _counts_oracle(pts, centers, 0.3))


def test_traverse_rejects_pallas_with_explanation():
    """The generic driver cannot host the kernel backend (prebuilt user
    closures can't be rebuilt inside the kernel) — the error must route
    users to the engine entry points."""
    bvh = _bvh(np.random.default_rng(0).uniform(0, 1, (8, 3)))
    qdata = (jnp.zeros((2,), jnp.int32),)
    with pytest.raises(ValueError, match="query_count"):
        traverse(bvh, qdata, lambda q, c, n: True,
                 lambda q, c, o, s: (c, False), 0, backend="pallas")


def test_jit_and_grad_safe_composition():
    """The engine call containing the pallas_call must trace under jit."""
    rng = np.random.default_rng(2)
    pts = rng.uniform(0, 1, (25, 3)).astype(np.float32)
    bvh = _bvh(pts)
    pred = within(jnp.asarray(rng.uniform(0, 1, (9, 3)).astype(np.float32)), 0.2)
    f = jax.jit(lambda b, p: query_count(b, p, backend="pallas"))
    np.testing.assert_array_equal(
        np.asarray(f(bvh, pred)),
        np.asarray(query_count(bvh, pred, backend="stackless")))


# --- no native lowering: a pointed error, never a silent interpret ----------

def test_native_compile_raises_the_lowering_gap():
    from repro.kernels.wavefront import LOWERING_GAP, wavefront_fill_round

    pts = np.random.default_rng(0).uniform(0, 1, (64, 3)).astype(np.float32)
    bvh = _bvh(pts)
    qdata = (jnp.arange(64, dtype=jnp.int32),)
    with pytest.raises(NotImplementedError, match="gather lowering"):
        wavefront_traverse(bvh, qdata, lambda tree: (None, None),
                           jnp.int32(0), interpret=False)
    with pytest.raises(NotImplementedError, match="gather lowering"):
        wavefront_fill_round(bvh, qdata, lambda tree: (None, None),
                             jnp.zeros((64,), jnp.int32), 4, interpret=False)
    assert "stackless" in LOWERING_GAP


def test_engine_dispatch_raises_where_kernels_compile_natively(monkeypatch):
    import sys
    engine = sys.modules["repro.core.query"]  # the package re-exports query()
    pts = np.random.default_rng(1).uniform(0, 1, (64, 3)).astype(np.float32)
    monkeypatch.setattr(engine, "INTERPRET", False)
    with pytest.raises(NotImplementedError, match="no native TPU lowering"):
        query_count(_bvh(pts), within(jnp.asarray(pts), 0.1),
                    backend="pallas")
