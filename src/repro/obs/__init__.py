"""Observability layer: device-side traversal stats and host-side span
tracing with Chrome-trace export.

See ``obs/stats.py`` (TraversalStats) and ``obs/trace.py`` (SpanTracer /
traced). Both are strictly opt-in: the engine's stats-off path stages the
identical jaxpr it did before this package existed (machine-checked by
``repro.staticcheck``'s ``stats_path_identity`` audit). Device-side, each
stage of the library runs under a ``jax.named_scope`` (``bvh.build``,
``dbscan.union``, ``halos.catalog``, ...), which names its ops in the
compiled program's metadata and so in a profiler trace.
"""
from repro.obs.stats import TraversalStats
from repro.obs.trace import (Span, SpanTracer, load_chrome_trace, span_tree,
                             traced)

__all__ = [
    "TraversalStats",
    "Span",
    "SpanTracer",
    "traced",
    "load_chrome_trace",
    "span_tree",
]
