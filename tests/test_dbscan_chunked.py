"""The eps min-label pass runs only the lanes its mask selects, in chunks of
``LANE_CHUNK`` lanes: its results, and fdbscan's, are bit-identical to one
direct engine call over every lane with the mask applied to the output."""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import generator
from repro.core import dbscan
from repro.core.bvh import build_bvh
from repro.core.query import query, within

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _direct(bvh, pts, eps, labels, core, mask, sentinel, order=None):
    """The pass as one lockstep loop over all n lanes, mask on the output."""
    def fn(best, _qi, j, _d2):
        return (jnp.where(core[j], jnp.minimum(best, labels[j]), best),
                jnp.bool_(False))

    sentinel = jnp.asarray(sentinel, labels.dtype)
    out = query(bvh, within(pts, jnp.asarray(eps, pts.dtype)), fn, sentinel)
    return jnp.where(mask, out, sentinel)


def _bvh(pts):
    lo, hi = pts.min(0) - 1e-4, pts.max(0) + 1e-4
    return build_bvh(jnp.asarray(pts), jnp.asarray(lo), jnp.asarray(hi))


def _mask(kind, n, core, chunk, rng):
    if kind == "all":
        return np.ones(n, bool)
    if kind == "none":
        return np.zeros(n, bool)
    if kind == "core":
        return core
    if kind == "noncore":
        return ~core
    if kind == "random":
        return rng.random(n) < 0.3
    k = {"chunk": chunk, "chunk+1": chunk + 1}[kind]
    m = np.zeros(n, bool)
    m[rng.choice(n, k, replace=False)] = True
    return m


# (n, chunk, mask, order); chunk None is the module's own LANE_CHUNK.
CASES = [
    (300, 64, "all", "identity"),
    (300, 64, "none", "identity"),
    (300, 64, "core", "leaf"),
    (300, 64, "noncore", "leaf"),
    (300, 64, "random", "random"),
    (300, 64, "chunk", "leaf"),
    (300, 64, "chunk+1", "random"),
    (256, 64, "chunk+1", "identity"),
    (40, 64, "all", "leaf"),           # n below the chunk
    (40, 64, "random", "random"),
    (None, None, "chunk", "leaf"),      # n = LANE_CHUNK + 300
    (None, None, "chunk+1", "random"),
    (None, None, "core", "leaf"),
    (None, None, "all", "identity"),
]


@pytest.mark.parametrize("n,chunk,kind,order", CASES)
def test_chunked_pass_matches_direct_query(monkeypatch, n, chunk, kind, order):
    if chunk is None:
        chunk = dbscan.LANE_CHUNK
        n = chunk + 300
    monkeypatch.setattr(dbscan, "LANE_CHUNK", chunk)
    rng = np.random.default_rng(n * 31 + chunk)
    pts = rng.uniform(0, 1, (n, 3)).astype(np.float32)
    eps = np.float32(1.3 * n ** (-1 / 3))
    bvh = _bvh(pts)
    core = rng.random(n) < 0.7
    labels = rng.permutation(n).astype(np.int32)
    mask = _mask(kind, n, core, chunk, rng)
    perm = {"identity": None, "leaf": bvh.leaf_perm,
            "random": jnp.asarray(rng.permutation(n).astype(np.int32))}[order]
    args = (bvh, jnp.asarray(pts), eps, jnp.asarray(labels),
            jnp.asarray(core), jnp.asarray(mask), n)
    got = jax.jit(lambda *a: dbscan.min_core_label_on(*a, order=perm))(*args)
    want = jax.jit(_direct)(*args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (np.asarray(got)[~mask] == n).all()
    c = min(chunk, n)
    assert int(dbscan.traversed_lanes(jnp.asarray(mask))) == \
        -(-int(mask.sum()) // c) * c


def _fdbscan_with(min_label_pass, pts, eps):
    """fdbscan traced afresh with ``min_core_label_on`` replaced."""
    saved = dbscan.min_core_label_on
    dbscan.min_core_label_on = min_label_pass
    try:
        return jax.jit(lambda p, e: dbscan.fdbscan.__wrapped__(p, e, 2))(
            jnp.asarray(pts), np.float32(eps))
    finally:
        dbscan.min_core_label_on = saved


def _snapshot(mix_name, n):
    mix = generator.load_mix(ROOT / "bench" / "traffic" / f"{mix_name}.json")
    mix = dict(mix, particles_per_chip=n)
    return generator.snapshot(mix, 1, 3_000_000_017, 0, 0.168)


@pytest.mark.parametrize("mix_name,n,chunk", [
    ("clustered", 2048, None), ("clustered", 2048, 256),
    ("uniform", 4096, None), ("uniform", 4096, 512),
])
def test_fdbscan_chunked_equals_unchunked(monkeypatch, mix_name, n, chunk):
    snap = _snapshot(mix_name, n)
    if chunk is not None:
        monkeypatch.setattr(dbscan, "LANE_CHUNK", chunk)
    got = _fdbscan_with(dbscan.min_core_label_on, snap.points, snap.eps)
    want = _fdbscan_with(_direct, snap.points, snap.eps)
    np.testing.assert_array_equal(np.asarray(got.labels),
                                  np.asarray(want.labels))
    np.testing.assert_array_equal(np.asarray(got.core_mask),
                                  np.asarray(want.core_mask))
    assert int(got.num_rounds) == int(want.num_rounds)
    assert 0.0 < float(got.lane_share) <= 1.0


def test_lane_share_counts_whole_chunks(monkeypatch):
    """10 core points (two tight clusters of 6 and 4) and 30 isolated ones at
    minPts 2, chunks of 8: a union round runs 16 lanes, the border pass 32."""
    monkeypatch.setattr(dbscan, "LANE_CHUNK", 8)
    rng = np.random.default_rng(0)
    iso = np.stack(np.meshgrid(np.arange(5), np.arange(3), np.arange(2),
                               indexing="ij"), -1).reshape(-1, 3) * 0.2 + 0.05
    a = np.array([0.9, 0.9, 0.9]) + rng.uniform(0, 0.002, (6, 3))
    b = np.array([0.9, 0.1, 0.9]) + rng.uniform(0, 0.002, (4, 3))
    pts = np.concatenate([iso, a, b]).astype(np.float32)
    res = jax.jit(lambda p: dbscan.fdbscan.__wrapped__(p, np.float32(0.01), 2))(
        jnp.asarray(pts))
    assert int(np.asarray(res.core_mask).sum()) == 10
    r = int(res.num_rounds)
    assert r >= 1
    assert float(res.lane_share) == pytest.approx(
        (16 * r + 32) / (40 * (r + 1)), rel=1e-6)
    others = [dbscan.dbscan_graph_cc(jnp.asarray(pts), 0.01, 2),
              dbscan.fdbscan_pair(jnp.asarray(pts), 0.01, 2),
              dbscan.fdbscan_densebox(jnp.asarray(pts), 0.01, 2)]
    assert [float(o.lane_share) for o in others] == [1.0, 1.0, 1.0]


DIST_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    import numpy as np, jax, jax.numpy as jnp
    sys.path.insert(0, {tests!r})
    from test_dbscan_chunked import _direct
    from conftest import make_clustered_points
    from repro.core import dbscan, distributed
    from repro.core.distributed import dbscan_distributed, slab_partition

    mesh = jax.make_mesh((4,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    pts, _ = slab_partition(make_clustered_points(np.random.default_rng(2),
                                                  1024), 4)

    def run():
        jax.clear_caches()
        res = dbscan_distributed(jnp.asarray(pts), 0.05, 2, mesh=mesh,
                                 halo_cap=512)
        assert not bool(res.halo_overflow)
        return np.asarray(res.labels), np.asarray(res.core_mask)

    dbscan.LANE_CHUNK = 32
    chunked = run()
    dbscan.min_core_label_on = distributed.min_core_label_on = _direct
    direct = run()
    assert (chunked[0] == direct[0]).all(), "labels"
    assert (chunked[1] == direct[1]).all(), "core mask"
    print("DIST_CHUNKED_OK")
""")


def test_distributed_labels_unchanged_by_chunking():
    """``dbscan_distributed`` on 4 virtual devices (its local ``union_rounds``
    and its min-label rounds over local ∪ ghost trees) gives the same labels
    with chunked passes as with direct ones."""
    tests_dir = str(ROOT / "tests")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT / "src"), str(ROOT)])
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", DIST_SCRIPT.format(tests=tests_dir)],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "DIST_CHUNKED_OK" in out.stdout
