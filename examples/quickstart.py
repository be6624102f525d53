"""Quickstart: the paper's contribution in 30 lines.

Cluster a cosmology-style point cloud with FDBSCAN (the ArborX algorithm,
§4.3.3), tour the unified query API behind it (§4.1), then cross-check
against the TPU-native tiled-grid implementation. The FDBSCAN and
query-API sections run on CPU in seconds; the final grid section runs the
Pallas kernels in interpret mode on CPU and takes several minutes (it is
the fast path on the TPU target).

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core.dbscan import fdbscan
from repro.core.fdbscan_grid import fdbscan_grid, grid_dims_for
from repro.data.pipeline import hacc_benchmark_epsilon, make_clustered_points

# --- the paper's benchmark setup, downscaled -------------------------------
# (CPU demo scale: the paper's ε = b(V/n)^{1/3} at n=37M maps to very fine
# grids; on CPU-interpret we keep the same density REGIME by shrinking n
# and widening ε so the stencil grid stays small.)
n = 512
points = make_clustered_points(np.random.default_rng(0), n)
eps = 4 * hacc_benchmark_epsilon(volume=1.0, n_particles=n)  # b (V/n)^{1/3}
min_pts = 2                                                  # FOF

# --- faithful tier: BVH + stackless traversal + fused union-find -----------
res = fdbscan(jnp.asarray(points), eps, min_pts)
n_noise = int((np.asarray(res.labels) < 0).sum())
print(f"FDBSCAN:  {int((np.asarray(res.labels) >= 0).sum())} clustered, "
      f"{n_noise} noise, union rounds={int(res.num_rounds)}")

# --- the query API ----------------------------------------------------------
# FDBSCAN above is a thin client of ONE engine (the paper's §4.1 story):
# query(index, predicates, callback). Build the tree once, then dispatch any
# predicate against it — fused callbacks, CSR outputs, kNN — all through the
# same entry point (with Morton query sorting a flip of a switch).
from repro.core.bvh import build_bvh
from repro.core.geometry import scene_bounds
from repro.core.query import (nearest, query, query_count, query_csr,
                              query_csr_device, within)

jp = jnp.asarray(points)
lo, hi = scene_bounds(jp)
bvh = build_bvh(jp, lo, hi)

# 1. range counts with early exit (DBSCAN's core test IS this call;
#    counts saturate at stop_at — only the >= min_pts verdict matters):
counts = query_count(bvh, within(jp, eps), stop_at=min_pts)

# 2. full neighbor lists as count-then-fill CSR. With no capacity, one host
#    sync sizes the output exactly:
csr = query_csr(bvh, within(jp, eps))
offsets, indices = csr.offsets, csr.indices

# 2b. the DEVICE-RESIDENT variant (the ArborX 2.0 contract): pass a capacity
#     bound and the count → exclusive scan → scatter-fill pipeline stays on
#     device end to end — jit-traceable, no sync, overflow reported as a
#     flag. This is the protocol the sharded pipeline builds on (see
#     examples/distributed_halo_finding.py: the whole build → ghost exchange
#     → query → DBSCAN → catalog merge chain runs inside ONE shard_map
#     region with zero host round-trips).
dev = query_csr_device(bvh, within(jp, eps), capacity=64 * n)
assert not bool(dev.overflowed)
assert int(dev.total) == int(csr.offsets[-1])

# 3. a fused callback: sum of neighbor indices, no storage at all —
#    must agree with the CSR materialization of the same predicate:
def cb(acc, q_idx, obj_idx, d2):   # invoked per ε-pair, d2 = squared dist
    return acc + obj_idx, jnp.bool_(False)
sums = query(bvh, within(jp, eps), cb, jnp.int32(0), sort_queries=True)
assert int(sums.sum()) == int(indices.sum())

# 4. k nearest neighbors through the same dispatcher:
nn = query(bvh, nearest(jp[:8], k=4))

print(f"query API: {int((counts >= min_pts).sum())} core points, "
      f"CSR nnz={int(offsets[-1])}, knn[0]={np.asarray(nn.indices[0])}")

# 5. picking a backend. Every spatial call above takes `backend=`:
#
#      backend="stackless"  (default) vmapped rope traversal — one scalar
#                           while-loop per query, XLA schedules the batch.
#      backend="stack"      explicit-stack twin, mainly a correctness oracle.
#      backend="pallas"     ONE batched Pallas wavefront kernel: a block of
#                           Morton-sorted queries advances through the tree
#                           in lockstep, rope hops + fused callback inside a
#                           single while-loop — the GPU-style traversal the
#                           paper credits for its largest wins (§4). Pick it
#                           on TPU targets; on CPU it runs in interpret mode
#                           (correct but slow — CI exercises it that way).
#
#    All three return identical results for query / query_count / query_csr
#    / query_csr_device / query_csr_buffered, including `with_stats=` and
#    `start_nodes=` (cell-grid pruned starts). `nearest()` is the exception:
#    its priority-queue carry is stackless/stack only for now.
counts_p = query_count(bvh, within(jp, eps), backend="pallas",
                       sort_queries=True)
assert bool(jnp.array_equal(counts_p, query_count(bvh, within(jp, eps),
                                                  sort_queries=True)))

# --- observability -----------------------------------------------------------
# Every §4 win in the paper (early termination, stackless ropes, pair
# traversal) came from MEASURING traversal behaviour. `with_stats=True` on
# any spatial query returns a device-resident TraversalStats alongside the
# result — per-query nodes visited, AABB/leaf tests, callback hits, early
# exits and depth high-water mark — with ZERO cost when off (the stats-off
# jaxpr is machine-checked identical to the uninstrumented engine):
from repro.obs import SpanTracer

counts_s, stats = query_count(bvh, within(jp, eps), stop_at=min_pts,
                              with_stats=True)
tot = stats.totals()   # still on device; sums/maxes of the per-query columns
print(f"traversal: {int(tot['nodes_visited'])} nodes, "
      f"{int(tot['callback_hits'])} hits, "
      f"{int(tot['early_exits'])} early exits, depth {int(tot['max_depth'])}")

# Host-side spans fence async dispatch (block_until_ready) so durations
# cover the device work, and export Chrome-trace JSON for ui.perfetto.dev;
# under jax.profiler.trace they also land in the profiler's trace, beside
# the device ops, whose stages carry jax.named_scope names (bvh.build,
# dbscan.union, ...). The sharded pipelines take `tracer=` directly
# (halo_pipeline_traced, dbscan_distributed, InsituAnalyzer):
tracer = SpanTracer()
with tracer.span("quickstart_query", n=n) as sp:
    sp.fence(query_count(bvh, within(jp, eps)))
tracer.export("trace_quickstart.json")      # load in ui.perfetto.dev

# --- static checks ----------------------------------------------------------
# The device-discipline rules this file leans on (no dense staging, no host
# syncs, shard_map jits only via the _maybe_jit gate, consumed overflow
# flags, guarded min-image folds) are machine-checked by `repro.staticcheck`:
#
#   PYTHONPATH=src python -m repro.staticcheck                 # AST lint R1-R4
#   PYTHONPATH=src python -m repro.staticcheck --jaxpr --fast  # + jaxpr audits
#   PYTHONPATH=src python -m repro.staticcheck --json report.json
#
# Exit status is nonzero iff any finding fired; findings carry file:line
# anchors, and a `# staticcheck: <token>` pragma (overflow-ok, minimage-ok,
# bvh-loop-ok, shard-jit-ok, ignore) opts out a deliberate exception. The
# same rules are importable — prove the device CSR call above never stages
# the dense (q × max_count) buffer, then watch the lint catch the ROADMAP
# item 3 f32 trap in a snippet:
from repro.staticcheck import audit_jaxpr, lint_source, no_dense_intermediate

assert audit_jaxpr(
    lambda b: query_csr_device(b, within(jp, eps), capacity=64 * n),
    (bvh,), [no_dense_intermediate(n * n)]) == []

bad = ("import jax.numpy as jnp\n"
       "def fold(d, L):\n"
       "    return d - jnp.round(d / L) * L\n")
print("staticcheck demo:", lint_source(bad, "snippet.py")[0])

# --- scale-safety checks ----------------------------------------------------
# Everything above ran at n=512, but the paper's target is N=1e9 points on
# 64 shards. The third staticcheck layer — an abstract interpreter over the
# traced jaxpr — re-reads the staged toy sizes as SYMBOLIC exascale sizes
# and propagates a value interval per array, proving the W rules without
# materializing anything: W1 index-width (a signed int escapes its dtype),
# W2 precision (float quantization past 2^mantissa — the min-image trap of
# ROADMAP item 3), W3 bounds & routes (unprovable gather indices, broken
# ppermute tables). Here it derives that the int32 CSR offsets of the very
# call audited above overflow at 64e9 total hits:
from repro.staticcheck import SymbolicScale, analyze, scale_for
from repro.staticcheck.lattice import Ival

scale = SymbolicScale(dims=scale_for(n, 10**9, {64 * n: 64 * 10**9}))
rep = analyze(
    lambda b, c: query_csr_device(b, within(jp, eps), capacity=64 * n,
                                  counts=c),
    (bvh, counts), name="quickstart_csr_int32", scale=scale,
    input_ivals=[None, Ival(0, 2048)])
print("scale-safety demo:", rep.findings[0].message)
# The fix is the satellite API: query_csr_device(..., index_dtype=jnp.int64)
# under x64 analyzes clean — CI pins the widened production configs (and
# the seeded broken twins) via `python -m repro.staticcheck --absint`.

# --- TPU-native tier: ε-cell binning + MXU stencil kernels -----------------
# (interpret-mode on CPU: this section takes several minutes here.)
dims = grid_dims_for(np.zeros(3), np.ones(3), eps)
res_g, overflowed = fdbscan_grid(
    jnp.asarray(points), eps, min_pts,
    scene_lo=np.zeros(3, np.float32), grid_dims=dims, capacity=256)
assert not bool(overflowed)
print(f"TPU grid: {int((np.asarray(res_g.labels) >= 0).sum())} clustered "
      f"({int(np.prod(dims))} cells x 27-stencil)")

# --- same partitions? -------------------------------------------------------
from repro.core.ref_numpy import labels_equivalent
assert labels_equivalent(np.asarray(res.labels), np.asarray(res_g.labels),
                         np.asarray(res.core_mask))
print("faithful tier and TPU tier agree.")
