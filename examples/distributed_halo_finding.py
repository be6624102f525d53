"""Distributed halo finding example: HACC's MPI domain decomposition as
shard_map + collectives, on 8 simulated devices — first stage by stage
(DBSCAN, then catalog), then the whole thing again through
``halo_pipeline_sharded``: build → ghost exchange → query → DBSCAN →
catalog merge → SO masses fused into ONE shard_map region with zero host
round-trips between stages.

NOTE: sets XLA_FLAGS before importing jax — run as a script, not import.

  PYTHONPATH=src python examples/distributed_halo_finding.py
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.distributed import dbscan_distributed, slab_partition
from repro.core.ref_numpy import core_mask_ref, dbscan_ref, labels_equivalent
from repro.data.pipeline import hacc_benchmark_epsilon, make_clustered_points

mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
n = 1024
pts = make_clustered_points(np.random.default_rng(1), n)
eps = hacc_benchmark_epsilon(1.0, n)

# Domain decomposition: each "rank" owns a contiguous slab along x.
pts_sorted, _ = slab_partition(pts, 8)
res = dbscan_distributed(jnp.asarray(pts_sorted), eps, 2, mesh=mesh,
                         halo_cap=1024)
print(f"distributed FOF over 8 shards: rounds={int(res.rounds)} "
      f"halo_overflow={bool(res.halo_overflow)}")
labels = np.asarray(res.labels)
print(f"{int((labels >= 0).sum())} clustered / {n}, "
      f"{len(np.unique(labels[labels >= 0]))} clusters")

# cross-check against the single-node oracle
ref = dbscan_ref(pts_sorted, eps, 2)
core = core_mask_ref(pts_sorted, eps, 2)
assert labels_equivalent(labels, ref, core)
print("matches the single-node oracle.")

# --- the production step: sharded labels -> merged halo catalog -------------
from repro.halos import halo_catalog, halo_catalog_sharded

vel = np.random.default_rng(2).standard_normal((n, 3)).astype(np.float32)
cat = halo_catalog_sharded(jnp.asarray(pts_sorted), jnp.asarray(vel),
                           res.labels, mesh=mesh, capacity=128, min_count=10)
single = halo_catalog(jnp.asarray(pts_sorted), jnp.asarray(vel), res.labels,
                      capacity=128, min_count=10)
assert int(cat.num_halos) == int(single.num_halos)
np.testing.assert_allclose(np.asarray(cat.center), np.asarray(single.center),
                           atol=1e-5)
nh = int(cat.num_halos)
top = np.argsort(-np.asarray(cat.count[:nh]))[:5]
print(f"merged catalog across 8 shards: {nh} halos (>=10 particles); top 5:")
for h in top:
    print(f"  root={int(cat.root[h]):4d} count={int(cat.count[h]):4d} "
          f"center={np.round(np.asarray(cat.center[h]), 3)} "
          f"vdisp={float(cat.vdisp[h]):.3f} rmax={float(cat.rmax[h]):.4f}")
print("sharded catalog == single-device catalog.")

# --- the fused pipeline: everything above in ONE shard_map region -----------
# (per-shard BVH build, ε-ghost exchange, engine-traversal DBSCAN, catalog
# merge, max-radius pass, SO masses — one device launch, no host syncs.)
from repro.halos import halo_pipeline_sharded

pipe = halo_pipeline_sharded(
    jnp.asarray(pts_sorted), jnp.asarray(vel), eps, 2, mesh=mesh,
    capacity=128, halo_cap=1024, min_count=10, so_delta=200.0)
assert labels_equivalent(np.asarray(pipe.labels), ref, core)
assert int(pipe.catalog.num_halos) == nh
np.testing.assert_allclose(np.asarray(pipe.catalog.center),
                           np.asarray(cat.center), atol=1e-5)
nb = int(np.asarray(pipe.so.bracketed).sum())
print(f"fused pipeline: rounds={int(pipe.rounds)}, {nh} halos, "
      f"SO masses bracketed for {nb}; one shard_map region end to end.")
