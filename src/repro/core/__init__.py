"""repro.core — the paper's contribution: ArborX-style geometric search +
DBSCAN clustering, reimplemented for JAX/TPU.

Faithful tier (GPU-paper semantics, validated against the numpy oracle):
  morton, bvh (LBVH + ropes), query (the UNIFIED ENGINE, §4.1: predicate
  constructors within/intersects_box/nearest/ray, stackless/stack/pair
  backends, fused callbacks with early exit, two-pass CSR + buffered
  single-pass output protocols, Morton query sorting), union_find, and
  its thin clients: dbscan (graph-CC, FDBSCAN, FDBSCAN-pair,
  FDBSCAN-DenseBox), knn, emst (Boruvka Euclidean MST), correlation
  (2-pt pair counts), interpolate (MLS), raycast — the full ArborX §3.2
  functionality surface. ``traversal`` keeps the pre-engine entry points
  as compatibility shims.

Also: cell_grid + fdbscan_grid (tiled ε-stencil DBSCAN on the MXU, backed
by repro.kernels.pairwise; a dense ε-grid, so only for small boxes — about
4e8 cells at n = 2^21 under the paper's ε), and distributed (shard_map
multi-device DBSCAN). The halo-finding path on a TPU is ``fdbscan``.
"""
from repro.core.bvh import Bvh, build_bvh, build_bvh_objects, SENTINEL
from repro.core.cell_grid import CellGrid, build_cell_grid, cell_box
from repro.core.dbscan import (
    NOISE,
    DbscanResult,
    count_neighbors,
    dbscan_graph_cc,
    fdbscan,
    fdbscan_densebox,
    fdbscan_pair,
    min_core_label_on,
    union_rounds,
)
from repro.core.geometry import Aabb, aabb_of_points
from repro.core.morton import morton32, morton64, normalize_points
from repro.core.query import (
    BufferedCsr,
    DeviceCsr,
    IntersectsBox,
    Nearest,
    NearestResult,
    Ray,
    RayResult,
    Within,
    intersects_box,
    nearest,
    node_reduce,
    query,
    query_count,
    query_csr,
    query_csr_buffered,
    query_csr_device,
    query_fixed,
    ray,
    within,
)
from repro.core.traversal import (
    pair_traverse_sphere,
    traverse_sphere_stack,
    traverse_sphere_stackless,
)
from repro.core.knn import KnnResult, knn
from repro.core.emst import EmstResult, emst
from repro.core.correlation import pair_count_histogram, two_point_correlation
from repro.core.interpolate import mls_interpolate
from repro.core.raycast import RayHits, raycast, raycast_all
from repro.core import union_find

__all__ = [
    "Bvh", "build_bvh", "build_bvh_objects", "SENTINEL",
    "CellGrid", "build_cell_grid", "cell_box",
    "NOISE", "DbscanResult", "count_neighbors",
    "min_core_label_on", "union_rounds",
    "dbscan_graph_cc", "fdbscan", "fdbscan_densebox", "fdbscan_pair",
    "Aabb", "aabb_of_points",
    "morton32", "morton64", "normalize_points",
    "Within", "IntersectsBox", "Nearest", "Ray",
    "NearestResult", "RayResult", "DeviceCsr", "BufferedCsr",
    "within", "intersects_box", "nearest", "ray",
    "query", "query_count", "query_csr", "query_csr_buffered",
    "query_csr_device", "query_fixed",
    "node_reduce",
    "pair_traverse_sphere", "traverse_sphere_stack", "traverse_sphere_stackless",
    "KnnResult", "knn", "EmstResult", "emst",
    "pair_count_histogram", "two_point_correlation",
    "mls_interpolate", "RayHits", "raycast", "raycast_all",
    "union_find",
]
