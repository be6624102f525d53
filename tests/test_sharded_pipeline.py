"""End-to-end sharded pipeline tests (subprocess: the multi-device XLA flag
must be set before jax imports).

Covers the reusable sharded-query layer (``sharded_neighbor_csr``: per-shard
BVH build → ppermute ghost exchange → device-resident CSR with GLOBAL ids)
and the one-region fused pipeline (``halo_pipeline_sharded``: build →
exchange → DBSCAN → catalog merge → SO masses), including the acceptance
check that the fused pipeline performs ZERO device→host transfers after
warmup (``repro.staticcheck.assert_no_host_transfers(..., guard="d2h")``).
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap


def _run(code: str, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


_PRELUDE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
    import numpy as np, jax, jax.numpy as jnp
    mesh = jax.make_mesh(({n},), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
""")


def test_sharded_neighbor_csr_matches_oracle():
    """Global-id CSR rows from the sharded layer == brute-force ε-graph."""
    code = _PRELUDE.format(n=4) + textwrap.dedent("""
        from repro.core.distributed import sharded_neighbor_csr, slab_partition

        rng = np.random.default_rng(3)
        pts = rng.uniform(0, 1, (256, 3)).astype(np.float32)
        pts, _ = slab_partition(pts, 4)
        eps = 0.12
        res = sharded_neighbor_csr(jnp.asarray(pts), eps, capacity=4096,
                                   mesh=mesh, halo_cap=128)
        assert not bool(res.overflowed), "capacity overflow"
        offs = np.asarray(res.offsets)          # (4, n_loc+1)
        idx = np.asarray(res.indices)           # (4, capacity) global ids
        n_loc = offs.shape[1] - 1

        d2 = ((pts[:, None, :] - pts[None, :, :]) ** 2).sum(-1)
        adj = d2 <= eps * eps                   # self included
        for s in range(4):
            for q in range(n_loc):
                got = np.sort(idx[s, offs[s, q]:offs[s, q + 1]])
                want = np.flatnonzero(adj[s * n_loc + q])
                assert (got == want).all(), (s, q, got, want)
        total = int(np.asarray(res.total).sum())
        assert total == int(adj.sum())
        print("CSR_OK")
    """)
    assert "CSR_OK" in _run(code)


def test_halo_pipeline_matches_staged_path():
    """Fused one-region pipeline == staged dbscan_ref + single-node catalog,
    and the SO-mass stage brackets real halos."""
    code = _PRELUDE.format(n=4) + textwrap.dedent("""
        import sys
        sys.path.insert(0, {tests!r})
        from conftest import make_clustered_points
        from repro.core.distributed import slab_partition
        from repro.core.ref_numpy import (core_mask_ref, dbscan_ref,
                                          labels_equivalent)
        from repro.halos import halo_catalog, halo_pipeline_sharded

        rng = np.random.default_rng(7)
        pts = make_clustered_points(rng, 512)
        pts, _ = slab_partition(pts, 4)
        vel = rng.standard_normal((512, 3)).astype(np.float32)
        eps = 0.05
        pipe = halo_pipeline_sharded(
            jnp.asarray(pts), jnp.asarray(vel), eps, 2, mesh=mesh,
            capacity=128, halo_cap=512, min_count=5, so_delta=200.0)
        assert not bool(pipe.halo_overflow)

        ref = dbscan_ref(pts, eps, 2)
        core = core_mask_ref(pts, eps, 2)
        labels = np.asarray(pipe.labels)
        assert (np.asarray(pipe.core_mask) == core).all(), "core mask"
        assert labels_equivalent(labels, ref, core), "labels"

        single = halo_catalog(jnp.asarray(pts), jnp.asarray(vel),
                              pipe.labels, capacity=128, min_count=5)
        assert int(pipe.catalog.num_halos) == int(single.num_halos)
        nh = int(single.num_halos)
        np.testing.assert_allclose(np.asarray(pipe.catalog.center)[:nh],
                                   np.asarray(single.center)[:nh], atol=1e-5)
        np.testing.assert_allclose(np.asarray(pipe.catalog.count)[:nh],
                                   np.asarray(single.count)[:nh])
        np.testing.assert_allclose(np.asarray(pipe.catalog.rmax)[:nh],
                                   np.asarray(single.rmax)[:nh], atol=1e-5)
        assert int(np.asarray(pipe.so.bracketed)[:nh].sum()) > 0
        print("PIPE_OK", nh)
    """).format(tests=os.path.dirname(os.path.abspath(__file__)))
    assert "PIPE_OK" in _run(code)


def test_halo_pipeline_zero_host_round_trips():
    """After warmup, the whole build→exchange→DBSCAN→catalog chain runs with
    device→host transfers DISALLOWED — the one-shard_map-region guarantee."""
    code = _PRELUDE.format(n=2) + textwrap.dedent("""
        from repro.core.distributed import slab_partition
        from repro.halos import halo_pipeline_sharded
        from repro.staticcheck import assert_no_host_transfers

        rng = np.random.default_rng(1)
        pts = rng.uniform(0, 1, (128, 3)).astype(np.float32)
        pts, _ = slab_partition(pts, 2)
        vel = rng.standard_normal((128, 3)).astype(np.float32)
        jp, jv = jnp.asarray(pts), jnp.asarray(vel)

        run = lambda: halo_pipeline_sharded(jp, jv, 0.08, 2, mesh=mesh,
                                            capacity=128, halo_cap=64,
                                            min_count=2)
        # warmup runs outside the guard; the guarded rerun is the contract
        out = assert_no_host_transfers(run, guard="d2h")
        assert int(out.catalog.num_halos) >= 1
        print("GUARD_OK")
    """)
    assert "GUARD_OK" in _run(code)
