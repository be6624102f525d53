"""Distributed DBSCAN (shard_map) tests — run in a subprocess so the
8-device XLA flag doesn't leak into this process."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
    import numpy as np, jax, jax.numpy as jnp
    mesh = jax.make_mesh(({n},), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    import sys
    sys.path.insert(0, "{tests}")
    from conftest import make_clustered_points
    from repro.core.distributed import dbscan_distributed, slab_partition
    from repro.core.ref_numpy import dbscan_ref, core_mask_ref, labels_equivalent

    rng = np.random.default_rng({seed})
    pts = make_clustered_points(rng, {npts})
    pts_sorted, order = slab_partition(pts, {n})
    for min_pts in (2, 5):
        res = dbscan_distributed(jnp.asarray(pts_sorted), {eps}, min_pts,
                                 mesh=mesh, halo_cap=512)
        assert not bool(res.halo_overflow), "halo overflow"
        ref = dbscan_ref(pts_sorted, {eps}, min_pts)
        core = core_mask_ref(pts_sorted, {eps}, min_pts)
        assert (np.asarray(res.core_mask) == core).all(), "core mask"
        assert labels_equivalent(np.asarray(res.labels), ref, core), "labels"
    print("DIST_OK")
""")


def _run(n_dev: int, npts: int, seed: int, eps: float = 0.05) -> str:
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(tests_dir), "src")
    env.pop("XLA_FLAGS", None)
    code = SCRIPT.format(n=n_dev, npts=npts, seed=seed, eps=eps,
                         tests=tests_dir)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.parametrize("n_dev", [2, 8])
def test_distributed_matches_oracle(n_dev):
    assert "DIST_OK" in _run(n_dev, 512, seed=0)


def test_distributed_cluster_spanning_all_shards():
    """A dense filament crossing every slab must merge into one cluster."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        from repro.core.distributed import dbscan_distributed
        n = 512
        x = np.linspace(0.01, 0.99, n).astype(np.float32)
        pts = np.stack([x, np.full(n, .5, np.float32),
                        np.full(n, .5, np.float32)], 1)
        res = dbscan_distributed(jnp.asarray(pts), 0.01, 2, mesh=mesh,
                                 halo_cap=64)
        labels = np.asarray(res.labels)
        assert (labels == labels[0]).all() and labels[0] >= 0, labels[:20]
        print("SPAN_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SPAN_OK" in out.stdout
