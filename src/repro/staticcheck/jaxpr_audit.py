"""Layer 1: jaxpr invariant audits — trace a callable and enforce the
repo's device-discipline rules on every sub-jaxpr.

This generalizes the ad-hoc walker that used to live inline in
``tests/test_device_csr.py``: given a callable + example args, walk ALL
sub-jaxprs (while_loop/scan/cond bodies, pallas_call kernels, nested
nested jit regions) and apply pluggable rules:

* ``no_dense_intermediate(max_elems)`` — no intermediate array at or
  above a size budget. This is how O(n²) staging regressions (the dense
  ``(q, max_count)`` fill buffer the scan-then-scatter CSR replaced, the
  dense neighbor matrices the sharded DBSCAN replaced) are caught at
  trace time, before they cost memory at run time.
* ``no_host_transfer()`` — no host-interaction primitives
  (``callback``-family, infeed/outfeed, ``device_put``) anywhere in a
  device pipeline. The trace-time complement of the runtime
  ``transfer_guard`` checks (see :func:`assert_no_host_transfers`).
* ``bounded_recompiles(cap)`` — drive a workload sweep through a
  shape-signature counter and assert the number of DISTINCT compiled
  shapes stays under ``cap`` (the serving tier's fixed-bucket premise:
  bucketed batching must collapse arbitrary request sizes onto a few
  compiled programs).

Rules are callables ``rule(closed_jaxpr, name) -> list[Finding]`` so new
invariants slot in without touching the walker.
"""
from __future__ import annotations

from typing import Callable, Iterable, Iterator

import numpy as np
import jax

from repro.staticcheck.findings import Finding

__all__ = [
    "iter_subjaxprs",
    "iter_eqns",
    "max_intermediate_elems",
    "no_dense_intermediate",
    "no_host_transfer",
    "audit_jaxpr",
    "jaxpr_op_signature",
    "count_compile_signatures",
    "bounded_recompiles",
    "assert_no_host_transfers",
]

# Primitive names that imply host interaction inside a traced program.
# Matched exactly, plus any primitive whose name contains "callback"
# (pure_callback / io_callback / debug_callback across JAX versions).
_HOST_PRIMS = frozenset({"device_put", "infeed", "outfeed", "host_call"})


def iter_subjaxprs(jaxpr) -> Iterator:
    """Yield ``jaxpr`` and every sub-jaxpr reachable through eqn params
    (while/scan/cond branches, nested jit regions, pallas kernels, ...)."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for val in eqn.params.values():
            items = val if isinstance(val, (tuple, list)) else [val]
            for it in items:
                inner = getattr(it, "jaxpr", it)
                if hasattr(inner, "eqns"):
                    yield from iter_subjaxprs(inner)


def iter_eqns(jaxpr) -> Iterator:
    for sub in iter_subjaxprs(jaxpr):
        yield from sub.eqns


def _out_elems(eqn) -> int:
    """Largest output array of one eqn, in elements (0 if shapeless)."""
    biggest = 0
    for v in eqn.outvars:
        aval = getattr(v, "aval", None)
        shape = getattr(aval, "shape", None)
        if shape:
            biggest = max(biggest, int(np.prod(shape)))
    return biggest


def _closed(fn_or_jaxpr, args):
    if hasattr(fn_or_jaxpr, "eqns") or hasattr(fn_or_jaxpr, "jaxpr"):
        return fn_or_jaxpr
    return jax.make_jaxpr(fn_or_jaxpr)(*args)


def max_intermediate_elems(fn, args=()) -> int:
    """Largest intermediate array (elements) over all sub-jaxprs — the
    quantity ``no_dense_intermediate`` budgets. Accepts a callable +
    example args or an already-made (closed) jaxpr."""
    closed = _closed(fn, args)
    jaxpr = getattr(closed, "jaxpr", closed)
    return max((_out_elems(eqn) for eqn in iter_eqns(jaxpr)), default=0)


def no_dense_intermediate(max_elems: int) -> Callable:
    """Rule: every intermediate must stay strictly under ``max_elems``.

    Pick the budget as the size of the dense object the pipeline is NOT
    allowed to stage — e.g. ``q * max_count`` for CSR fills, ``n * n``
    for neighbor pipelines."""
    budget = int(max_elems)

    def rule(closed_jaxpr, name: str) -> list[Finding]:
        jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
        worst_eqn, worst = None, 0
        for eqn in iter_eqns(jaxpr):
            elems = _out_elems(eqn)
            if elems > worst:
                worst_eqn, worst = eqn, elems
        if worst >= budget:
            return [Finding(
                rule="no-dense-intermediate", path=f"<jaxpr:{name}>", line=0,
                message=(f"intermediate of {worst} elems >= budget {budget} "
                         f"(primitive {worst_eqn.primitive.name!r}): the "
                         f"pipeline is staging a dense buffer"))]
        return []

    return rule


def no_host_transfer() -> Callable:
    """Rule: no callback/infeed/outfeed/device_put-class primitive may
    appear anywhere in the traced program."""

    def rule(closed_jaxpr, name: str) -> list[Finding]:
        jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
        findings = []
        seen = set()
        for eqn in iter_eqns(jaxpr):
            pname = eqn.primitive.name
            if (pname in _HOST_PRIMS or "callback" in pname) \
                    and pname not in seen:
                seen.add(pname)
                findings.append(Finding(
                    rule="no-host-transfer", path=f"<jaxpr:{name}>", line=0,
                    message=(f"host-interaction primitive {pname!r} inside a "
                             f"device pipeline")))
        return findings

    return rule


def audit_jaxpr(fn, args, rules: Iterable[Callable], *,
                name: str | None = None) -> list[Finding]:
    """Trace ``fn(*args)`` and apply each rule to the resulting jaxpr.
    Returns the concatenated findings ([] == the invariants hold)."""
    name = name or getattr(fn, "__name__", "fn")
    closed = _closed(fn, args)
    findings: list[Finding] = []
    for rule in rules:
        findings.extend(rule(closed, name))
    return findings


def jaxpr_op_signature(fn, args=()) -> tuple:
    """Stable op-level signature of a traced program: the sequence of
    ``(primitive name, output avals)`` over the jaxpr and every sub-jaxpr
    (while/scan bodies included), in trace order. Two callables with equal
    signatures stage the same ops on the same shapes/dtypes — the equality
    the ``stats_path_identity`` audit uses to prove the engine's
    ``with_stats=False`` path is op-for-op the pre-obs program. Accepts a
    callable + example args or an already-closed jaxpr."""
    closed = _closed(fn, args)
    jaxpr = getattr(closed, "jaxpr", closed)
    return tuple(
        (eqn.primitive.name, tuple(str(v.aval) for v in eqn.outvars))
        for eqn in iter_eqns(jaxpr))


# --- recompile budget (trace a workload sweep) ------------------------------

def _signature(args) -> tuple:
    leaves = jax.tree.leaves(args)
    return tuple((tuple(np.shape(x)), str(getattr(x, "dtype", type(x).__name__)))
                 for x in leaves)


def count_compile_signatures(sweep: Iterable[tuple]) -> int:
    """Number of DISTINCT (shape, dtype) signatures across a sweep of
    example-arg tuples — each distinct signature is one jit cache entry."""
    return len({_signature(args) for args in sweep})


def bounded_recompiles(fn, sweep: Iterable[tuple], cap: int, *,
                       name: str | None = None,
                       check_trace: bool = True) -> list[Finding]:
    """Rule: running ``fn`` over every args-tuple in ``sweep`` must compile
    at most ``cap`` distinct programs (the fixed-bucket serving premise).

    With ``check_trace`` each distinct signature is also traced once, so a
    sweep that would fail to compile is caught here too."""
    name = name or getattr(fn, "__name__", "fn")
    sweep = list(sweep)
    seen: dict[tuple, tuple] = {}
    for args in sweep:
        seen.setdefault(_signature(args), args)
    if check_trace:
        for args in seen.values():
            jax.make_jaxpr(fn)(*args)
    if len(seen) > cap:
        return [Finding(
            rule="bounded-recompiles", path=f"<jaxpr:{name}>", line=0,
            message=(f"{len(seen)} distinct compiled shapes over a "
                     f"{len(sweep)}-point sweep exceeds the cap of {cap}: "
                     f"bucket the workload to fixed shapes"))]
    return []


# --- runtime complement: the transfer-guard assertion -----------------------

def assert_no_host_transfers(fn, *args, guard: str = "all", warmup: bool = True):
    """Run ``fn(*args)`` with JAX's transfer guard set to ``disallow`` and
    return the (block_until_ready'd) result — the single source of truth for
    the repo's "zero host round-trips after warmup" assertions.

    ``guard="all"`` disallows every implicit transfer
    (``jax.transfer_guard``); ``guard="d2h"`` disallows only device→host
    (``jax.transfer_guard_device_to_host``) — the one-shard_map-region
    guarantee. With ``warmup`` the first call (compilation, which may
    legally sync) happens outside the guard."""
    if guard == "all":
        ctx = jax.transfer_guard("disallow")
    elif guard == "d2h":
        ctx = jax.transfer_guard_device_to_host("disallow")
    else:
        raise ValueError(f"guard must be 'all' or 'd2h', got {guard!r}")
    if warmup:
        jax.block_until_ready(fn(*args))
    with ctx:
        out = fn(*args)
        jax.block_until_ready(out)
    return out
