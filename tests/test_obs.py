"""Observability layer (repro.obs): TraversalStats oracles vs brute force,
span tracer nesting + Chrome-trace round trip and its spans in the
profiler's trace, the library's device-side stage scopes in compiled HLO,
and the totals of observability-bearing results (including per-shard
columns from a shard_map region)."""
from __future__ import annotations

import re
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.bvh import build_bvh
from repro.core.query import (
    nearest,
    query,
    query_count,
    query_csr_device,
    within,
)
from repro.obs import (
    Span,
    SpanTracer,
    TraversalStats,
    load_chrome_trace,
    span_tree,
    traced,
)


def _bvh(pts):
    lo = pts.min(0) - 1e-4
    hi = pts.max(0) + 1e-4
    return build_bvh(jnp.asarray(pts), jnp.asarray(lo), jnp.asarray(hi))


def _points(n=257, seed=0):
    return np.random.default_rng(seed).uniform(0, 1, (n, 3)).astype(np.float32)


def _brute_counts(pts, eps):
    d2 = ((pts[:, None] - pts[None]) ** 2).sum(-1, dtype=np.float32)
    return (d2 <= np.float32(eps) ** 2).sum(1)


# --- TraversalStats oracles -------------------------------------------------

@pytest.mark.parametrize("backend", ["stackless", "stack"])
def test_stats_oracles_vs_bruteforce(backend):
    """callback_hits == brute-force pair counts; leaf_tests >= hits;
    nodes_visited == aabb_tests + leaf_tests (every loop iteration is
    exactly one bounding-volume test); counts identical to stats-off."""
    pts = _points()
    eps = 0.15
    bvh = _bvh(pts)
    want = _brute_counts(pts, eps)

    counts, stats = query_count(bvh, within(jnp.asarray(pts), eps),
                                backend=backend, with_stats=True)
    plain = query_count(bvh, within(jnp.asarray(pts), eps), backend=backend)
    np.testing.assert_array_equal(np.asarray(counts), want)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(plain))
    np.testing.assert_array_equal(np.asarray(stats.callback_hits), want)

    s = {k: np.asarray(v) for k, v in zip(TraversalStats._fields, stats)}
    assert np.all(s["leaf_tests"] >= s["callback_hits"])
    np.testing.assert_array_equal(
        s["nodes_visited"], s["aabb_tests"] + s["leaf_tests"])
    assert np.all(s["max_depth"] >= 1)
    # nothing terminates early without a short-circuiting callback
    assert not np.any(s["early_exits"])


@pytest.mark.parametrize("backend", ["stackless", "stack"])
def test_stats_early_exit_matches_shortcircuit(backend):
    """With stop_at=1 every query that has any neighbour (always true for a
    self-join: the query point itself) short-circuits, and the early-exit
    column says exactly which ones did."""
    pts = _points(n=128, seed=3)
    bvh = _bvh(pts)
    counts, stats = query_count(bvh, within(jnp.asarray(pts), 0.1),
                                stop_at=1, backend=backend, with_stats=True)
    want_exit = _brute_counts(pts, 0.1) >= 1
    np.testing.assert_array_equal(np.asarray(stats.early_exits), want_exit)
    assert np.all(np.asarray(counts) <= 1)
    # short-circuiting must visit no more nodes than the full traversal
    _, full = query_count(bvh, within(jnp.asarray(pts), 0.1),
                          backend=backend, with_stats=True)
    assert np.all(np.asarray(stats.nodes_visited)
                  <= np.asarray(full.nodes_visited))


def test_stats_pair_backend_half_counts():
    """Pair traversal visits each unordered pair once: total callback hits
    equal the brute-force pair count, and the invariants still hold."""
    pts = _points(n=96, seed=5)
    eps = 0.2
    bvh = _bvh(pts)

    def cb(c, qidx, obj, d2):
        return c + 1, jnp.bool_(False)

    out, stats = query(bvh, within(jnp.asarray(pts), eps), cb, jnp.int32(0),
                       backend="pair", with_stats=True)
    want_pairs = int((_brute_counts(pts, eps) - 1).sum()) // 2
    assert int(np.asarray(stats.callback_hits).sum()) == want_pairs
    s = {k: np.asarray(v) for k, v in zip(TraversalStats._fields, stats)}
    np.testing.assert_array_equal(
        s["nodes_visited"], s["aabb_tests"] + s["leaf_tests"])
    assert np.all(s["leaf_tests"] >= s["callback_hits"])


def test_stats_sort_queries_unsorts_stats_rows():
    """With engine-level Morton query sorting the stats rows must come back
    in ORIGINAL query order, aligned with the outputs."""
    pts = _points(n=200, seed=7)
    eps = 0.12
    bvh = _bvh(pts)
    counts, stats = query_count(bvh, within(jnp.asarray(pts), eps),
                                sort_queries=True, with_stats=True)
    want = _brute_counts(pts, eps)
    np.testing.assert_array_equal(np.asarray(counts), want)
    np.testing.assert_array_equal(np.asarray(stats.callback_hits), want)


def test_stats_compose_with_jit():
    pts = _points(n=64, seed=1)
    bvh = _bvh(pts)

    @jax.jit
    def run(p):
        return query_count(bvh, within(p, 0.2), with_stats=True)

    counts, stats = run(jnp.asarray(pts))
    np.testing.assert_array_equal(np.asarray(stats.callback_hits),
                                  _brute_counts(pts, 0.2))
    tot = stats.totals()
    assert int(tot["nodes_visited"]) == int(tot["aabb_tests"]) + int(tot["leaf_tests"])


def test_stats_rejects_priority_queue_protocols():
    pts = _points(n=32)
    bvh = _bvh(pts)
    with pytest.raises(ValueError, match="priority-queue"):
        query(bvh, nearest(jnp.asarray(pts), 4), with_stats=True)


# --- span tracer ------------------------------------------------------------

def test_tracer_nesting_and_roundtrip(tmp_path):
    tracer = SpanTracer(process_name="test")
    with tracer.span("outer", n=4) as sp:
        assert isinstance(sp, Span)
        with tracer.span("inner"):
            time.sleep(0.002)
        val = sp.fence(jnp.arange(8).sum())
    assert int(val) == 28
    tracer.instant("marker", step=1)
    tracer.counter("hits", total=3)

    path = tracer.export(str(tmp_path / "trace.json"))
    events = load_chrome_trace(path)
    assert [e["name"] for e in events] == ["outer", "inner"]
    tree = span_tree(events)
    assert tree["outer"] == ["inner"]
    outer = events[0]
    inner = events[1]
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert outer["args"] == {"n": 4, "depth": 0}
    # non-span events survive the export (raw stream, not load_chrome_trace)
    import json
    raw = json.loads(open(path).read())["traceEvents"]
    assert {e["ph"] for e in raw} == {"M", "X", "i", "C"}


def test_traced_none_is_passthrough():
    calls = []

    def fn(x, y=1):
        calls.append((x, y))
        return x + y

    assert traced(None, "noop", fn, 2, y=3) == 5
    tracer = SpanTracer()
    assert traced(tracer, "yes", fn, 2, y=3, span_args={"k": 1}) == 5
    assert calls == [(2, 3), (2, 3)]
    assert tracer.events[0]["name"] == "yes"
    assert tracer.events[0]["args"]["k"] == 1


def test_tracer_exception_unwind():
    """A span that exits via exception still closes (no dangling stack) and
    skips its fences (no block_until_ready on the failure path)."""
    tracer = SpanTracer()
    with pytest.raises(RuntimeError):
        with tracer.span("outer"):
            with tracer.span("boom"):
                raise RuntimeError("x")
    assert [e["name"] for e in tracer.events] == ["boom", "outer"]
    assert tracer._stack == []


# --- span tracer on the profiler's clock ----------------------------------

def test_span_lands_in_profiler_trace(tmp_path):
    """A span recorded under jax.profiler.trace is a host event of the
    .xplane.pb, beside the device ops, and still exports to Chrome JSON."""
    tracer = SpanTracer()
    with jax.profiler.trace(str(tmp_path)):
        with tracer.span("obs_test_outer"):
            with tracer.span("obs_test_inner") as sp:
                sp.fence(jnp.arange(16.0).sum())
    files = sorted(tmp_path.rglob("*.xplane.pb"))
    assert files
    pd = jax.profiler.ProfileData.from_file(str(files[-1]))
    host = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
            for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events}
    assert {"obs_test_outer", "obs_test_inner"} <= set(host)
    (o0, o1), (i0, i1) = host["obs_test_outer"], host["obs_test_inner"]
    assert o0 <= i0 <= i1 <= o1
    assert [e["name"] for e in tracer.events] == ["obs_test_inner",
                                                 "obs_test_outer"]


# --- device-side stage scopes ----------------------------------------------

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"^[a-z]+\.[a-z_]+$")

STAGE_SCOPES = [
    "bvh.build", "bvh.morton_sort", "bvh.hierarchy",
    "dbscan.core_pass", "dbscan.union", "dbscan.border_pass",
    "dbscan.finish",
    "halos.catalog", "halos.potential_pass", "halos.center_argmin",
    "halos.so_bisect",
]
DBSCAN_SCOPES = ["dbscan.core_pass", "dbscan.union", "dbscan.border_pass",
                 "dbscan.finish"]


def _hlo_scopes(text: str) -> set[str]:
    """Library scopes named by the op_name metadata of an HLO module."""
    return {c for m in _OP_NAME.finditer(text) for c in m.group(1).split("/")
            if _SCOPE.match(c)}


@pytest.fixture(scope="module")
def step_hlo():
    """Optimized HLO of the analysis step's library entry points, small n."""
    from repro.core.dbscan import fdbscan
    from repro.halos import halo_catalog, most_bound_centers, so_masses

    n = 256
    pts = jnp.asarray(_points(n=n, seed=5))
    eps = np.float32(0.08)
    lab = jax.ShapeDtypeStruct((n,), jnp.int32)
    progs = {
        "fdbscan": (lambda p, e: fdbscan(p, e, 2), (pts, eps)),
        "halo_catalog": (lambda p, v, l: halo_catalog(
            p, v, l, capacity=32, min_count=2), (pts, pts, lab)),
        "most_bound_centers": (lambda p, h, e: most_bound_centers(
            p, h, e, capacity=32), (pts, lab, eps)),
        "so_masses": (lambda p, c, v: so_masses(p, c, v, iters=4),
                      (pts, pts[:8], jnp.ones(8, bool))),
    }
    return {k: jax.jit(f).lower(*a).compile().as_text()
            for k, (f, a) in progs.items()}


@pytest.mark.parametrize("scope", STAGE_SCOPES)
def test_stage_scope_in_compiled_step(step_hlo, scope):
    assert scope in set().union(*map(_hlo_scopes, step_hlo.values()))


def test_union_while_carries_scope(step_hlo):
    """The union fixpoint's loop op itself, not only its body, carries
    dbscan.union, so the loop's own device time counts toward it."""
    whiles = [_OP_NAME.search(line).group(1)
              for line in step_hlo["fdbscan"].splitlines()
              if re.search(r"\swhile\(", line) and _OP_NAME.search(line)]
    assert any(w.endswith("dbscan.union/while") for w in whiles)


def test_build_sort_under_morton_sort(step_hlo):
    """Each tree build sorts under bvh.morton_sort: fdbscan builds once."""
    sorts = [_OP_NAME.search(line).group(1)
             for line in step_hlo["fdbscan"].splitlines()
             if re.search(r"\ssort\(", line) and _OP_NAME.search(line)]
    assert len([s for s in sorts if "/bvh.morton_sort/" in s]) == 1


@pytest.mark.parametrize("variant", ["fdbscan_pair", "fdbscan_densebox",
                                     "dbscan_graph_cc"])
def test_dbscan_variants_share_stage_scopes(variant):
    from repro.core import dbscan

    pts = jnp.asarray(_points(n=128, seed=6))
    fn = getattr(dbscan, variant)
    text = jax.jit(lambda p: fn(p, 0.1, 2)).lower(pts).compile().as_text()
    assert set(DBSCAN_SCOPES) | {"bvh.build"} <= _hlo_scopes(text)


# --- observability-bearing results, read directly -------------------------

def test_registry_observe_known_types():
    """DeviceCsr totals and TraversalStats.totals() agree with brute force;
    the totals are device scalars until read."""
    pts = _points(n=64, seed=2)
    bvh = _bvh(pts)
    want = float(_brute_counts(pts, 0.2).sum())
    csr = query_csr_device(bvh, within(jnp.asarray(pts), 0.2), capacity=4096)
    _, stats = query_count(bvh, within(jnp.asarray(pts), 0.2), with_stats=True)

    assert float(csr.total) == want
    assert not bool(csr.overflowed)
    tot = stats.totals()
    assert all(isinstance(v, jax.Array) for v in tot.values())
    assert float(tot["callback_hits"]) == want
    assert float(tot["nodes_visited"]) == (
        float(tot["aabb_tests"]) + float(tot["leaf_tests"]))
    assert float(tot["max_depth"]) >= 1.0


def test_registry_shard_map_column():
    """Stats produced inside a shard_map region, reduced with .psum, total
    the same as the plain path."""
    from jax.sharding import PartitionSpec as P

    pts = _points(n=64, seed=4)
    bvh = _bvh(pts)
    mesh = jax.make_mesh((1,), ("data",))

    def shard_fn(p):
        _, st = query_count(bvh, within(p, 0.2), with_stats=True)
        return st.psum("data")

    stats = jax.shard_map(shard_fn, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data"), check_vma=False)(jnp.asarray(pts))
    _, plain = query_count(bvh, within(jnp.asarray(pts), 0.2), with_stats=True)
    assert float(stats.totals()["callback_hits"]) == float(
        _brute_counts(pts, 0.2).sum())
    for key, val in plain.totals().items():
        assert float(stats.totals()[key]) == float(val), key
