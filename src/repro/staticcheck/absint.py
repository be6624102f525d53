"""Scale-safety abstract interpreter over closed jaxprs.

``repro.staticcheck``'s third layer: where the jaxpr audits gate program
STRUCTURE and the AST lint gates source idioms, this layer gates program
VALUES — it walks a traced jaxpr once, propagating an interval per array
(``lattice.Ival``), and asks whether the program still holds together when
the staged toy shapes are re-read as **symbolic exascale sizes** (N=1e9
points, 64 shards) without retracing.

Rule families
-------------

* **W1 index-width** — a *signed* integer op whose output interval escapes
  its dtype at symbolic N (int32 ``counts → cumsum → offsets`` CSR
  overflow, ``shard * n_local + i`` global-id overflow, narrowing
  ``convert_element_type`` truncation). Unsigned arithmetic *wraps*
  (two's-complement), so deliberate wraparound — Morton magic-number
  multiplies — stays silent; a finding fires only at the first eqn whose
  inputs were still representable.
* **W2 precision** — a float quantization (``round`` / ``floor`` /
  ``ceil`` / float→int convert) whose operand magnitude reaches
  2^mantissa (2^24 f32): the ulp spacing exceeds 1 and integer rounding
  is meaningless — the machine-derived form of the ``round(BIG/L)*L ==
  BIG`` min-image trap (ROADMAP item 3). With ``precision_floor`` set, a
  subtraction of overlapping large-magnitude intervals (catastrophic
  cancellation) also fires when the ulp at the operands exceeds the
  floor.
* **W3 bounds & routes** — a gather/scatter staged with
  ``PROMISE_IN_BOUNDS`` whose index interval is not provably inside the
  (symbolic) indexed axis; CLIP / FILL_OR_DROP modes are the sentinel-
  padding idiom and stay silent. Plus the collective-route audit:
  ``ppermute`` route tables must be partial permutations (unique
  sources, unique destinations, ids within the mesh axis) and
  ``psum``/``pmax``/``pmin``/``all_gather`` axis names must name mesh
  axes of the enclosing ``shard_map``.

Symbolic sizes: stage the program at small *marker* sizes (e.g. n=254),
then analyze under ``SymbolicScale(dims={254: 10**9}, axes={"data": 64})``
— every shape and integer literal equal to a marker is re-read at the
symbolic size, so ``iota``/``cumsum``/``reduce_sum``/``axis_index``
bounds reflect the exascale run. ``scale_for(n, N)`` builds the marker
family {n, n±1, 2n-1, 2n-2} for BVH-shaped programs.

Soundness posture: unmodelled primitives and unstable while-loop carries
degrade to ``known=False`` fallbacks that never fire findings — false
negatives are possible, false positives are what the rules are built to
avoid. ``scan`` carries use linear widening (per-iteration drift × trip
count), so accumulator overflow in scans is still caught.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, NamedTuple

from repro.staticcheck import lattice as lat
from repro.staticcheck.findings import Finding
from repro.staticcheck.lattice import Ival

__all__ = [
    "SymbolicScale",
    "scale_for",
    "AbsintReport",
    "CollectiveUse",
    "analyze",
    "analyze_jaxpr",
    "audit_routes",
]

_WHILE_JOIN_ITERS = 4


def _fmt(x) -> str:
    """Exact display for integral bounds (an off-by-one W3 finding must
    not print as '[0, 1e+09] outside [0, 1e+09]')."""
    if isinstance(x, int) and abs(x) < 10**15:
        return str(x)
    if isinstance(x, float) and math.isfinite(x) and x.is_integer() \
            and abs(x) < 10**15:
        return str(int(x))
    return f"{x:.4g}"


class SymbolicScale(NamedTuple):
    """The staged-size → symbolic-size re-reading.

    ``dims``: marker dim/literal sizes → symbolic sizes (choose distinctive
    staged markers ≥ 64 so ordinary small constants never collide).
    ``axes``: mesh axis name → symbolic shard count (``axis_index`` /
    ``psum`` bounds). ``precision_floor``: enables the W2 cancellation rule
    at the given absolute-precision requirement (off when None).
    """
    dims: dict = {}
    axes: dict = {}
    precision_floor: float = None

    def dim(self, d: int) -> int:
        return int(self.dims.get(int(d), int(d)))

    def lit(self, v):
        """Re-read an integer literal that equals a marker size."""
        if isinstance(v, (int,)) and not isinstance(v, bool) and v in self.dims:
            return int(self.dims[v])
        return v

    def axis_size(self, name: str, staged: int) -> int:
        return int(self.axes.get(name, staged))


def scale_for(n: int, N: int, extra: dict | None = None) -> dict:
    """Marker family for a BVH-shaped program staged at ``n`` leaves:
    maps n, n±1 and the internal-node counts 2n-1 / 2n-2 to their
    symbolic counterparts. Merge ``extra`` marker→symbolic pairs on top."""
    dims = {n: N, n - 1: N - 1, n + 1: N + 1,
            2 * n - 1: 2 * N - 1, 2 * n - 2: 2 * N - 2}
    dims.update(extra or {})
    return dims


@dataclasses.dataclass
class AbsintReport:
    """One analysis run: findings + coverage counters."""
    name: str
    findings: list
    values_analyzed: int = 0
    eqns_visited: int = 0
    unknown_prims: int = 0
    collectives: list = dataclasses.field(default_factory=list)


class CollectiveUse(NamedTuple):
    """One collective op lifted out of a shard_map region."""
    prim: str              # "ppermute" | "psum" | "pmax" | ...
    axes: tuple            # axis names the op names
    perm: tuple            # ppermute route table ((src, dst), ...) or ()
    mesh_axes: dict        # enclosing mesh: axis name -> staged size


# ---------------------------------------------------------------------------
# The interpreter
# ---------------------------------------------------------------------------

def _aval_dtype(var):
    return getattr(var.aval, "dtype", None)


def _is_literal(v) -> bool:
    return type(v).__name__ == "Literal"


_SHAPE_ONLY = frozenset((
    "broadcast_in_dim", "reshape", "squeeze", "expand_dims", "transpose",
    "rev", "copy", "stop_gradient", "slice", "device_put",
    "sharding_constraint", "optimization_barrier", "reshard"))

# Subset safe for guard-refinement aliasing: lane i of the output is lane i
# (or a replica) of the input, so a lanewise predicate on the root still
# describes the aliased value. transpose/rev/slice reorder lanes and must
# not alias.
_LANE_SAFE = frozenset((
    "broadcast_in_dim", "reshape", "squeeze", "expand_dims", "copy",
    "stop_gradient", "device_put", "sharding_constraint",
    "optimization_barrier", "reshard"))


def _is_index_use(eqn, var) -> bool:
    """Is ``var`` consumed only at index-operand positions of this eqn?"""
    name = eqn.primitive.name
    if name == "gather" or name.startswith("scatter"):
        idx_pos = (1,)
    elif name == "dynamic_slice":
        idx_pos = tuple(range(1, len(eqn.invars)))
    elif name == "dynamic_update_slice":
        idx_pos = tuple(range(2, len(eqn.invars)))
    else:
        return False
    return (any(eqn.invars[j] is var for j in idx_pos)
            and all(eqn.invars[j] is not var
                    for j in range(len(eqn.invars)) if j not in idx_pos))


class _Interp:
    def __init__(self, scale: SymbolicScale, name: str, rules):
        self.scale = scale
        self.name = name
        self.rules = frozenset(rules)
        self.findings: dict = {}     # dedup key -> Finding
        self.report = AbsintReport(name=name, findings=[])
        self.mesh_stack: list = []   # enclosing shard_map meshes
        # Cross-level guard provenance: jnp.where stages as a nested jit
        # (primitive ``jit``) whose select_n sits one jaxpr BELOW the
        # comparison producing its predicate, so the same-level producer
        # scan cannot refine it. These maps are keyed by Var object (unique
        # per trace; jit-cached inner vars are re-bound at each _sub call
        # before use):
        self.guard_of: dict = {}     # cmp outvar -> (op, x_root, const)
        self.lin_of: dict = {}       # add/sub outvar -> (x_root, delta)
        self.alias: dict = {}        # var -> root var (shape-only, bindings)
        self.val_of: dict = {}       # var -> latest Ival (cross-level read)

    def _resolve(self, v):
        while v in self.alias:
            v = self.alias[v]
        return v

    # -- findings ----------------------------------------------------------

    def _emit(self, rule: str, ctx: str, message: str):
        # dedup per (rule, eqn path): loop fixpoint iterations revisit the
        # same eqn with growing intervals — keep the first firing only.
        key = (rule, ctx)
        if key not in self.findings:
            self.findings[key] = Finding(
                rule=rule, path=f"<absint:{self.name}>", line=0,
                message=f"[{ctx}] {message}")

    # -- env helpers -------------------------------------------------------

    def _read(self, env, v) -> Ival:
        if _is_literal(v):
            x = v.val
            try:
                x = x.item()
            except AttributeError:
                pass
            if isinstance(x, bool):
                return lat.const(int(x))
            if isinstance(x, int):
                return lat.const(self.scale.lit(x))
            if isinstance(x, float):
                return lat.const(x)
            return lat.dtype_top(_aval_dtype(v))
        return env.get(v, lat.dtype_top(_aval_dtype(v)))

    def _write(self, env, var, val: Ival):
        dtype = _aval_dtype(var)
        if dtype is not None and lat.is_unsigned_int(dtype):
            val = lat.wrap_unsigned(val, dtype)
        env[var] = val
        self.val_of[var] = val
        self.report.values_analyzed += 1

    def _sym_shape(self, var):
        return tuple(self.scale.dim(d) for d in getattr(var.aval, "shape", ())
                     if isinstance(d, int))

    def _only_deferred_uses(self, var, accept) -> bool:
        """True when every later use of ``var`` in the current jaxpr
        (followed transitively through shape-only eqns) satisfies
        ``accept(eqn, v)`` and never reaches a jaxpr output — the value's
        judgment is deferred to those consuming eqns."""
        eqns = getattr(self, "_cur_eqns", None)
        if eqns is None:
            return False
        outvars = getattr(self, "_cur_outvars", ())
        aliased = {var}
        if any(not _is_literal(v) and v in aliased for v in outvars):
            return False
        used = False
        for eqn in eqns[self._cur_idx + 1:]:
            hit = [v for v in eqn.invars if not _is_literal(v) and v in aliased]
            if not hit:
                continue
            if eqn.primitive.name in _SHAPE_ONLY:
                for o in eqn.outvars:
                    if any(o is ov for ov in outvars):
                        return False
                    aliased.add(o)
                continue
            if all(accept(eqn, v) for v in hit):
                used = True
                continue
            return False
        return used

    def _only_select_case_uses(self, var) -> bool:
        """Every later use of ``var`` is as a *case* of a ``select_n``
        (never the predicate, never any other eqn, never an output). Such a
        value is dead on the lanes where it is not selected, so its
        interval is judged after guard refinement at the select instead of
        at the producing eqn."""
        return self._only_deferred_uses(
            var, lambda eqn, v: (eqn.primitive.name == "select_n"
                                 and eqn.invars[0] is not v))

    def _only_gather_index_uses(self, var) -> bool:
        """Every later use of ``var`` is as the index operand of a
        gather/scatter (or a start index of a dynamic slice). jnp
        specializes index dtypes to the STAGED operand size — an int64
        index is narrowed to int32 when the toy array fits, an artifact
        that vanishes at real N. Judgment moves to the consuming eqn: a
        genuinely truncated index still fails the W3 bounds check there."""
        return self._only_deferred_uses(var, _is_index_use)

    # -- W-rule checks -----------------------------------------------------

    def _check_w1(self, eqn, ctx, ins, outs):
        if "W1" not in self.rules:
            return
        # fire only where the overflow FIRST happens: skip if an input
        # already escaped its own dtype (reported upstream).
        for v, iv in ins:
            dt = _aval_dtype(v)
            if dt is None or not iv.known:
                continue
            b = lat.int_bounds(dt)
            if b and lat.is_signed_int(dt) and (iv.lo < b[0] or iv.hi > b[1]):
                return
        # jnp's negative-index canonicalization computes ``i + size``
        # unconditionally and selects it only for i < 0 lanes — a value
        # consumed solely as select_n cases is judged at the select (where
        # guard refinement applies), not here.
        if all(self._only_select_case_uses(var) for var, _ in outs):
            return
        # jnp specializes gather/scatter index dtypes to the STAGED operand
        # size (int64 indices narrowed to int32 when the toy array fits) —
        # defer narrowing converts used only as indices to the consuming
        # eqn's W3 bounds check.
        if (eqn.primitive.name == "convert_element_type"
                and all(self._only_gather_index_uses(var)
                        for var, _ in outs)):
            return
        for var, iv in outs:
            dt = _aval_dtype(var)
            if dt is None or not iv.known or not lat.is_signed_int(dt):
                continue
            b = lat.int_bounds(dt)
            if b and (iv.lo < b[0] or iv.hi > b[1]):
                self._emit(
                    "W1-index-width", ctx,
                    f"{eqn.primitive.name}: {dt} result spans "
                    f"[{_fmt(iv.lo)}, {_fmt(iv.hi)}] at symbolic N — "
                    f"exceeds the dtype range [{_fmt(b[0])}, {_fmt(b[1])}]"
                    f"; widen the "
                    f"index dtype (index_dtype=int64 under x64) or annotate "
                    f"'# staticcheck: width-ok'")

    def _check_w2_quantize(self, eqn, ctx, operand_var, iv):
        if "W2" not in self.rules or not iv.known:
            return
        dt = _aval_dtype(operand_var)
        m = lat.mantissa_bits(dt)
        if m is None:
            return
        mag = iv.maxmag()
        if mag >= float(1 << m):
            self._emit(
                "W2-precision", ctx,
                f"{eqn.primitive.name}: quantizing a {dt} operand with "
                f"magnitude up to {mag:.4g} — ulp spacing "
                f"{lat.ulp_at(mag, dt):.4g} exceeds 1 beyond 2^{m}, so "
                f"integer rounding collapses (the round(BIG/L)*L == BIG "
                f"min-image trap); fold in f64 or clamp the operand first")

    def _check_w2_cancel(self, eqn, ctx, a_var, a, b_var, b, out):
        floor = self.scale.precision_floor
        if "W2" not in self.rules or floor is None:
            return
        dt = _aval_dtype(a_var)
        if not lat.is_float(dt) or not (a.known and b.known):
            return
        if not a.overlaps(b):
            return
        mag = min(a.maxmag(), b.maxmag())
        if mag == 0 or math.isinf(mag):
            return
        if lat.ulp_at(mag, dt) > floor:
            self._emit(
                "W2-precision", ctx,
                f"sub: catastrophic cancellation risk — {dt} operands of "
                f"magnitude ~{mag:.4g} may cancel, leaving absolute error "
                f"~{lat.ulp_at(mag, dt):.4g} > precision_floor={floor:.4g}; "
                f"use a two-pass/compensated formulation")

    def _check_w3_bounds(self, eqn, ctx, idx_iv: Ival, limit: int, kind: str):
        if "W3" not in self.rules or not idx_iv.known:
            return
        if idx_iv.lo < 0 or idx_iv.hi > limit - 1:
            self._emit(
                "W3-bounds", ctx,
                f"{kind}: PROMISE_IN_BOUNDS index interval "
                f"[{_fmt(idx_iv.lo)}, {_fmt(idx_iv.hi)}] is not provably "
                f"inside [0, {_fmt(limit - 1)}] at symbolic N — clip the "
                f"index or use "
                f"mode='clip'/'fill_or_drop' for sentinel padding")

    # -- jaxpr walk --------------------------------------------------------

    def run(self, jaxpr, consts, args, ctx: str, bind=None):
        env: dict = {}
        for var, iv in zip(jaxpr.constvars, consts):
            env[var] = iv
            self.val_of[var] = iv
        for var, iv in zip(jaxpr.invars, args):
            env[var] = iv if iv is not None else lat.dtype_top(_aval_dtype(var))
            self.val_of[var] = env[var]
        if bind is not None:
            # 1:1 call-site binding (nested jit): alias inner invars to their
            # outer arguments so guard provenance crosses the jaxpr edge.
            for ivar, ovar in zip(jaxpr.invars, bind):
                if not _is_literal(ovar):
                    self.alias[ivar] = self._resolve(ovar)
        prev = (getattr(self, "_cur_eqns", None), getattr(self, "_cur_idx", 0),
                getattr(self, "_cur_outvars", ()))
        self._cur_outvars = jaxpr.outvars
        for i, eqn in enumerate(jaxpr.eqns):
            self.report.eqns_visited += 1
            # the cursor lets select_n refinement find producer eqns
            self._cur_eqns, self._cur_idx = jaxpr.eqns, i
            _eqn(self, env, eqn, f"{ctx}.{i}" if ctx else str(i))
        self._cur_eqns, self._cur_idx, self._cur_outvars = prev
        return [self._read(env, v) for v in jaxpr.outvars]

    def _sub(self, closed, in_ivals, ctx, bind=None):
        inner = closed.jaxpr if hasattr(closed, "jaxpr") else closed
        consts = [self._read({}, v) if _is_literal(v) else
                  lat.dtype_top(_aval_dtype(v)) for v in inner.constvars]
        if hasattr(closed, "consts"):
            consts = [self._const_ival(c, v) for c, v in
                      zip(closed.consts, inner.constvars)]
        return self.run(inner, consts, in_ivals, ctx, bind=bind)

    def _const_ival(self, c, var) -> Ival:
        try:
            import numpy as np
            arr = np.asarray(c)
            if arr.size == 0:
                return lat.dtype_top(_aval_dtype(var))
            if arr.dtype.kind in "iub":
                return Ival(int(arr.min()), int(arr.max()), True)
            if arr.dtype.kind == "f":
                lo, hi = float(arr.min()), float(arr.max())
                if math.isnan(lo) or math.isnan(hi):
                    return lat.dtype_top(_aval_dtype(var))
                return Ival(lo, hi, True)
        except Exception:
            pass
        return lat.dtype_top(_aval_dtype(var))

    # -- refinement for canonicalized indexing -----------------------------

    def _refine_case(self, env, jaxpr_eqns, case_var, pred_var, guard, i):
        """Interval of ``case_var`` under the constraint ``pred_var`` ∈
        guard. One step of back-substitution: if the case IS the guarded
        var, meet; if it is ``guarded ± literal``, meet then shift. This is
        exactly the shape of jnp's negative-index canonicalization
        ``select_n(i < 0, i, i + n)`` — without it every well-bounded
        ``x[i]`` gather would look out-of-bounds under W3."""
        base = self._read(env, case_var)
        if _is_literal(case_var):
            return base
        if case_var is pred_var:
            m = lat.meet(base, guard)
            return m
        eqn = self._producer(jaxpr_eqns, case_var, i)
        if eqn is not None and eqn.primitive.name in ("add", "sub"):
            a, b = eqn.invars
            for x, off, sign in ((a, b, 1), (b, a, 1)):
                if x is pred_var and _is_literal(off):
                    d = self._read(env, off)
                    if not d.is_point():
                        continue
                    m = lat.meet(self._read(env, x), guard)
                    if m is None:
                        return None
                    shift = d.lo if eqn.primitive.name == "add" else -d.lo
                    if eqn.primitive.name == "sub" and x is b:
                        continue
                    return Ival(m.lo + shift, m.hi + shift, m.known)
        return base

    def _refine_case_global(self, env, case_var, x_root, xval, guard):
        """Cross-level variant of ``_refine_case``: the guarded var is
        identified by its alias ROOT rather than a same-level producer
        scan, so ``jnp.where(x < c, x, y)`` refines even when the select
        sits inside a nested jit and the cmp in its parent."""
        base = self._read(env, case_var)
        if _is_literal(case_var):
            return base
        root = self._resolve(case_var)
        if root is x_root:
            m = lat.meet(base, guard)
            return base if m is None else m
        lin = self.lin_of.get(root)
        if lin is not None and lin[0] is x_root:
            m = lat.meet(xval, guard)
            if m is not None:
                return Ival(m.lo + lin[1], m.hi + lin[1], m.known)
        return base

    @staticmethod
    def _producer(eqns, var, before):
        for eqn in eqns[:before][::-1]:
            if any(o is var for o in eqn.outvars):
                return eqn
        return None


# The per-eqn transfer dispatch lives outside the class body for length.

def _eqn(self: _Interp, env, eqn, ctx):
    prim = eqn.primitive.name
    scale = self.scale
    read = lambda v: self._read(env, v)
    ins = [read(v) for v in eqn.invars]

    def out(val: Ival, check_w1=True):
        for var in eqn.outvars:
            self._write(env, var, val)
        if check_w1:
            self._check_w1(eqn, ctx,
                           list(zip(eqn.invars, ins)),
                           [(v, val) for v in eqn.outvars])

    def fallback():
        self.report.unknown_prims += 1
        for var in eqn.outvars:
            self._write(env, var, lat.dtype_top(_aval_dtype(var)))

    # --- structured control flow ----------------------------------------
    if prim in ("jit", "closed_call", "core_call", "xla_call", "remat_call",
                "remat", "checkpoint", "custom_jvp_call", "custom_vjp_call",
                "custom_vjp_call_jaxpr"):
        closed = (eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
                  or eqn.params.get("fun_jaxpr"))
        if closed is None:
            return fallback()
        label = eqn.params.get("name", prim)
        outs = self._sub(closed, ins, f"{ctx}/{label}", bind=eqn.invars)
        for var, val in zip(eqn.outvars, outs):
            self._write(env, var, val)
        return

    if prim == "cond":
        branches = eqn.params["branches"]
        opers = ins[1:]
        branch_outs = [self._sub(br, opers, f"{ctx}/cond{k}")
                       for k, br in enumerate(branches)]
        for j, var in enumerate(eqn.outvars):
            val = branch_outs[0][j]
            for bo in branch_outs[1:]:
                val = lat.join(val, bo[j])
            self._write(env, var, val)
        return

    if prim == "while":
        return _while(self, env, eqn, ctx, ins)

    if prim == "scan":
        return _scan(self, env, eqn, ctx, ins)

    if prim == "shard_map":
        return _shard_map(self, env, eqn, ctx, ins)

    if prim == "pallas_call":
        # Pallas kernel bodies operate on Refs through load/store effects —
        # outside this value lattice (the jaxpr-audit walker in
        # staticcheck.audits does descend into them). Model the launch
        # soundly instead: every output covers its full dtype range, so
        # downstream W1 reasoning stays honest without claiming knowledge
        # of in-kernel values.
        for var in eqn.outvars:
            self._write(env, var, lat.dtype_top(_aval_dtype(var)))
        return

    # --- collectives ------------------------------------------------------
    if prim == "ppermute":
        axes = _axis_names(eqn)
        perm = tuple(tuple(p) for p in eqn.params.get("perm", ()))
        _record_collective(self, prim, axes, perm)
        # devices with no sender receive zeros
        return out(lat.join(ins[0], lat.const(0)))
    if prim in ("psum", "psum2", "psum_invariant"):
        axes = _axis_names(eqn)
        _record_collective(self, prim, axes, ())
        count = 1
        for a in axes:
            staged = self._mesh_size(a)
            count *= scale.axis_size(a, staged)
        return out(lat.scale_by_count(ins[0], count))
    if prim in ("pmax", "pmin", "all_gather", "pbroadcast", "all_to_all"):
        _record_collective(self, prim, _axis_names(eqn), ())
        return out(ins[0])
    if prim == "axis_index":
        a = eqn.params.get("axis_name")
        staged = self._mesh_size(a)
        return out(Ival(0, scale.axis_size(a, staged) - 1, True))

    # --- element-wise arithmetic -----------------------------------------
    if prim == "add":
        _note_lin(self, eqn, ins, 1)
        return out(lat.add(ins[0], ins[1]))
    if prim == "sub":
        self._check_w2_cancel(eqn, ctx, eqn.invars[0], ins[0],
                              eqn.invars[1], ins[1], None)
        _note_lin(self, eqn, ins, -1)
        return out(lat.sub(ins[0], ins[1]))
    if prim == "mul":
        return out(lat.mul(ins[0], ins[1]))
    if prim == "div":
        val = lat.div(ins[0], ins[1])
        dt = _aval_dtype(eqn.outvars[0])
        if lat.is_signed_int(dt) or lat.is_unsigned_int(dt):
            val = lat.truncate(val)  # lax.div truncates toward zero on ints
        return out(val)
    if prim == "rem":
        return out(lat.rem(ins[0], ins[1]))
    if prim == "neg":
        return out(lat.neg(ins[0]))
    if prim == "abs":
        return out(lat.iabs(ins[0]))
    if prim == "sign":
        return out(Ival(-1, 1, ins[0].known))
    if prim in ("min", "minimum"):
        return out(lat.imin(ins[0], ins[1]))
    if prim in ("max", "maximum"):
        return out(lat.imax(ins[0], ins[1]))
    if prim == "clamp":
        lo, x, hi = ins
        return out(lat.imax(lo, lat.imin(x, hi)))
    if prim == "square":
        return out(lat.mul(ins[0], ins[0]))
    if prim == "integer_pow":
        return _int_pow(out, ins[0], eqn.params.get("y", 1))
    if prim == "pow":
        return fallback()
    if prim == "sqrt":
        a = ins[0]
        return out(Ival(math.sqrt(max(a.lo, 0.0)),
                        math.sqrt(max(a.hi, 0.0)) if not math.isinf(a.hi)
                        else math.inf, a.known))
    if prim == "exp":
        return out(lat.monotonic(ins[0], lambda x: math.exp(min(x, 700.0))))
    if prim == "log":
        a = ins[0]
        return out(Ival(-math.inf if a.lo <= 0 else math.log(a.lo),
                        -math.inf if a.hi <= 0 else
                        (math.inf if math.isinf(a.hi) else math.log(a.hi)),
                        a.known))
    if prim in ("tanh", "erf", "sin", "cos"):
        return out(Ival(-1.0, 1.0, ins[0].known))
    if prim == "logistic":
        return out(Ival(0.0, 1.0, ins[0].known))
    if prim == "is_finite":
        return out(Ival(0, 1, True))
    if prim in ("floor", "ceil", "round", "nearbyint", "round_nearest_even"):
        self._check_w2_quantize(eqn, ctx, eqn.invars[0], ins[0])
        f = {"floor": lat.floor_op, "ceil": lat.ceil_op}.get(prim,
                                                             lat.round_op)
        return out(f(ins[0]))
    if prim == "convert_element_type":
        return _convert(self, env, eqn, ctx, ins, out)

    # --- bitwise ----------------------------------------------------------
    if prim == "and":
        dt = _aval_dtype(eqn.outvars[0])
        if getattr(dt, "name", str(dt)) == "bool":
            return out(Ival(0, 1, ins[0].known and ins[1].known))
        return out(lat.bit_and(ins[0], ins[1]))
    if prim == "or":
        dt = _aval_dtype(eqn.outvars[0])
        if getattr(dt, "name", str(dt)) == "bool":
            return out(Ival(0, 1, ins[0].known and ins[1].known))
        return out(lat.bit_or(ins[0], ins[1]))
    if prim == "xor":
        dt = _aval_dtype(eqn.outvars[0])
        if getattr(dt, "name", str(dt)) == "bool":
            return out(Ival(0, 1, ins[0].known and ins[1].known))
        return out(lat.bit_xor(ins[0], ins[1]))
    if prim == "not":
        return out(Ival(0, 1, ins[0].known))
    if prim == "shift_left":
        return out(lat.shift_left(ins[0], ins[1]))
    if prim == "shift_right_logical":
        return out(lat.shift_right(ins[0], ins[1], arithmetic=False))
    if prim == "shift_right_arithmetic":
        return out(lat.shift_right(ins[0], ins[1], arithmetic=True))
    if prim in ("clz", "population_count"):
        return out(Ival(0, 64, True))

    # --- comparisons ------------------------------------------------------
    if prim in ("eq", "ne", "lt", "le", "gt", "ge"):
        if prim in ("lt", "le", "gt", "ge"):
            a, b = eqn.invars
            av, bv = ins
            swap = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le"}
            if bv.is_point() and not _is_literal(a):
                self.guard_of[eqn.outvars[0]] = (prim, self._resolve(a),
                                                 bv.lo)
            elif av.is_point() and not _is_literal(b):
                self.guard_of[eqn.outvars[0]] = (swap[prim],
                                                 self._resolve(b), av.lo)
        return out(Ival(0, 1, True), check_w1=False)

    # --- shape/layout (interval-preserving) ------------------------------
    if prim in _SHAPE_ONLY or prim in ("reduce_precision", "real"):
        if prim in _LANE_SAFE and not _is_literal(eqn.invars[0]):
            self.alias[eqn.outvars[0]] = self._resolve(eqn.invars[0])
        return out(ins[0], check_w1=False)
    if prim == "concatenate":
        val = ins[0]
        for x in ins[1:]:
            val = lat.join(val, x)
        return out(val, check_w1=False)
    if prim == "pad":
        return out(lat.join(ins[0], ins[1]), check_w1=False)
    if prim == "select_n":
        return _select_n(self, env, eqn, ctx, ins, out)

    # --- index generation / reductions -----------------------------------
    if prim == "iota":
        dim = eqn.params.get("dimension", 0)
        shape = getattr(eqn.outvars[0].aval, "shape", (1,))
        n = scale.dim(shape[dim]) if shape else 1
        return out(Ival(0, max(n - 1, 0), True))
    if prim in ("reduce_sum", "cumsum"):
        count = _reduced_count(self, eqn, prim)
        return out(lat.scale_by_count(ins[0], count))
    if prim in ("reduce_max", "reduce_min", "cummax", "cummin"):
        return out(ins[0], check_w1=False)
    if prim in ("reduce_and", "reduce_or"):
        return out(Ival(0, 1, ins[0].known), check_w1=False)
    if prim in ("argmax", "argmin"):
        axes = eqn.params.get("axes", (0,))
        shape = getattr(eqn.invars[0].aval, "shape", (1,))
        n = max((scale.dim(shape[a]) for a in axes), default=1)
        return out(Ival(0, max(n - 1, 0), True))
    if prim == "reduce_prod":
        return fallback()
    if prim == "sort":
        # sort permutes values within each operand independently of keys
        for var, val in zip(eqn.outvars, ins):
            self._write(env, var, val)
        return
    if prim == "dot_general":
        dims = eqn.params.get("dimension_numbers")
        k = 1
        if dims:
            (lc, _), _ = dims
            shape = getattr(eqn.invars[0].aval, "shape", ())
            for a in lc:
                if a < len(shape):
                    k *= scale.dim(shape[a])
        prod = lat.mul(ins[0], ins[1])
        return out(lat.scale_by_count(prod, k))

    # --- gather / scatter -------------------------------------------------
    if prim == "gather":
        return _gather(self, env, eqn, ctx, ins, out)
    if prim.startswith("scatter"):
        return _scatter(self, env, eqn, ctx, ins, out)
    if prim == "dynamic_slice":
        return out(ins[0], check_w1=False)  # start indices are clamped
    if prim == "dynamic_update_slice":
        return out(lat.join(ins[0], ins[1]), check_w1=False)

    return fallback()


def _int_pow(out, a: Ival, y: int):
    y = int(y)
    if y < 0:
        return out(Ival(-math.inf, math.inf, a.known))
    if y == 0:
        return out(Ival(1, 1, a.known))

    def p(x):
        if math.isinf(x):
            return math.inf if (y % 2 == 0 or x > 0) else -math.inf
        try:
            return x ** y
        except OverflowError:
            return math.inf if (y % 2 == 0 or x > 0) else -math.inf

    cs = [p(a.lo), p(a.hi)]
    if y % 2 == 0 and a.lo < 0 < a.hi:
        cs.append(0)
    return out(Ival(min(cs), max(cs), a.known))


def _convert(self: _Interp, env, eqn, ctx, ins, out):
    src = ins[0]
    src_dt = _aval_dtype(eqn.invars[0])
    dst_dt = _aval_dtype(eqn.outvars[0])
    val = src
    if lat.is_float(src_dt) and (lat.is_signed_int(dst_dt)
                                 or lat.is_unsigned_int(dst_dt)):
        self._check_w2_quantize(eqn, ctx, eqn.invars[0], src)
        val = lat.truncate(src)
    if getattr(dst_dt, "name", str(dst_dt)) == "bool":
        val = Ival(0, 1, src.known)
    return out(val)


def _note_lin(self: _Interp, eqn, ins, sign):
    """Record ``out = x ± point`` linear provenance for guard-refinement
    back-substitution across jaxpr levels."""
    a, b = eqn.invars
    av, bv = ins
    if bv.is_point() and not _is_literal(a) and not math.isinf(bv.lo):
        self.lin_of[eqn.outvars[0]] = (self._resolve(a), sign * bv.lo)
    elif sign > 0 and av.is_point() and not _is_literal(b) \
            and not math.isinf(av.lo):
        self.lin_of[eqn.outvars[0]] = (self._resolve(b), av.lo)


def _select_n(self: _Interp, env, eqn, ctx, ins, out):
    pred_var = eqn.invars[0]
    cases = eqn.invars[1:]
    # Path-sensitive refinement when the predicate is a comparison of a
    # var against a point interval (jnp's negative-index canonicalization).
    jaxpr_eqns = getattr(self, "_cur_eqns", [])
    i = getattr(self, "_cur_idx", 0)
    pred_eqn = _Interp._producer(jaxpr_eqns, pred_var, i)
    if pred_eqn is None and len(cases) == 2 and not _is_literal(pred_var):
        # The jnp.where nested-jit shape: the select's predicate is a jaxpr invar
        # whose producing comparison sits in the PARENT jaxpr. Guard
        # provenance recorded at the cmp crosses the call edge via aliases.
        info = self.guard_of.get(self._resolve(pred_var))
        if info is not None:
            op, x_root, c = info
            xval = self.val_of.get(x_root)
            if xval is not None and xval.known:
                false_g, true_g = _guards(op, c)
                vals = []
                for case_var, guard in ((cases[0], false_g),
                                        (cases[1], true_g)):
                    if lat.meet(xval, guard) is None:
                        continue  # infeasible branch
                    vals.append(self._refine_case_global(
                        env, case_var, x_root, xval, guard))
                if vals:
                    v = vals[0]
                    for w in vals[1:]:
                        v = lat.join(v, w)
                    return out(v, check_w1=False)
    if (pred_eqn is not None and pred_eqn.primitive.name in
            ("lt", "le", "gt", "ge") and len(cases) == 2):
        x_var, c_var = pred_eqn.invars
        cval = self._read(env, c_var)
        xval = self._read(env, x_var)
        if cval.is_point() and not _is_literal(x_var):
            c = cval.lo
            op = pred_eqn.primitive.name
            false_g, true_g = _guards(op, c)
            vals = []
            for case_var, guard in ((cases[0], false_g), (cases[1], true_g)):
                g = lat.meet(xval, guard)
                if g is None:
                    continue  # infeasible branch
                r = self._refine_case(env, jaxpr_eqns, case_var, x_var,
                                      guard, i)
                if r is not None:
                    vals.append(r)
            if vals:
                v = vals[0]
                for w in vals[1:]:
                    v = lat.join(v, w)
                return out(v, check_w1=False)
    val = ins[1]
    for x in ins[2:]:
        val = lat.join(val, x)
    return out(val, check_w1=False)


def _guards(op: str, c):
    """(guard when pred False, guard when pred True) for ``x <op> c``."""
    inf = math.inf
    if op == "lt":
        return Ival(c, inf), Ival(-inf, c - 1 if isinstance(c, int) else c)
    if op == "le":
        return Ival(c + 1 if isinstance(c, int) else c, inf), Ival(-inf, c)
    if op == "gt":
        return Ival(-inf, c), Ival(c + 1 if isinstance(c, int) else c, inf)
    return Ival(-inf, c - 1 if isinstance(c, int) else c), Ival(c, inf)


def _mode_promises(eqn) -> bool:
    mode = eqn.params.get("mode")
    return "PROMISE_IN_BOUNDS" in str(mode)


def _gather(self: _Interp, env, eqn, ctx, ins, out):
    operand, idx = ins[0], ins[1]
    if _mode_promises(eqn):
        dn = eqn.params.get("dimension_numbers")
        shape = getattr(eqn.invars[0].aval, "shape", ())
        dims = getattr(dn, "start_index_map", (0,))
        limit = max((self.scale.dim(shape[d]) for d in dims
                     if d < len(shape)), default=1)
        self._check_w3_bounds(eqn, ctx, idx, limit, "gather")
    return out(operand, check_w1=False)


def _scatter(self: _Interp, env, eqn, ctx, ins, out):
    operand, idx, updates = ins[0], ins[1], ins[2] if len(ins) > 2 else ins[0]
    prim = eqn.primitive.name
    if _mode_promises(eqn):
        dn = eqn.params.get("dimension_numbers")
        shape = getattr(eqn.invars[0].aval, "shape", ())
        dims = getattr(dn, "scatter_dims_to_operand_dims", (0,))
        limit = max((self.scale.dim(shape[d]) for d in dims
                     if d < len(shape)), default=1)
        self._check_w3_bounds(eqn, ctx, idx, limit, prim)
    if prim in ("scatter-add", "scatter_add"):
        upd_shape = getattr(eqn.invars[2].aval, "shape", (1,)) \
            if len(eqn.invars) > 2 else (1,)
        n_upd = 1
        for d in upd_shape:
            n_upd *= self.scale.dim(d)
        # all updates may collapse onto one slot (segment-sum idiom)
        acc = lat.add(operand, lat.scale_by_count(updates, n_upd))
        return out(acc)
    if prim in ("scatter-min", "scatter_min"):
        # scatter-min only LOWERS slots: result ∈ [min(lo), operand.hi].
        # Keeping the operand's hi is what lets sentinel-valued updates
        # (union-find's ``where(core, m, n)``) min into ``parent`` without
        # parent's interval absorbing the out-of-range sentinel.
        return out(Ival(min(operand.lo, updates.lo), operand.hi,
                        operand.known and updates.known), check_w1=False)
    if prim in ("scatter-max", "scatter_max"):
        return out(Ival(operand.lo, max(operand.hi, updates.hi),
                        operand.known and updates.known), check_w1=False)
    return out(lat.join(operand, updates), check_w1=False)


def _reduced_count(self: _Interp, eqn, prim) -> int:
    shape = getattr(eqn.invars[0].aval, "shape", (1,))
    if prim == "reduce_sum":
        axes = eqn.params.get("axes", tuple(range(len(shape))))
    else:  # cumsum: the scanned axis
        axes = (eqn.params.get("axis", 0),)
    count = 1
    for a in axes:
        if a < len(shape):
            count *= self.scale.dim(shape[a])
    return max(count, 1)


def _axis_names(eqn):
    for key in ("axes", "axis_name", "axis_index_groups"):
        v = eqn.params.get(key)
        if key == "axes" and v:
            return tuple(a for a in v if isinstance(a, str)) or tuple(v)
        if key == "axis_name" and v is not None:
            return v if isinstance(v, tuple) else (v,)
    return ()


def _record_collective(self: _Interp, prim, axes, perm):
    mesh_axes = dict(self.mesh_stack[-1]) if self.mesh_stack else {}
    self.report.collectives.append(CollectiveUse(
        prim=prim, axes=tuple(a for a in axes if a is not None),
        perm=perm, mesh_axes=mesh_axes))


def _while(self: _Interp, env, eqn, ctx, ins):
    p = eqn.params
    cn, bn = p["cond_nconsts"], p["body_nconsts"]
    body = p["body_jaxpr"]
    cond = p["cond_jaxpr"]
    cond_consts = ins[:cn]
    body_consts = ins[cn:cn + bn]
    carry = list(ins[cn + bn:])
    for it in range(_WHILE_JOIN_ITERS):
        outs = self._sub(body, body_consts + carry, f"{ctx}/while")
        new = [lat.join(c, o) for c, o in zip(carry, outs)]
        if all(n == c for n, c in zip(new, carry)):
            break
        carry = new
    else:
        # unstable components degrade to unknown (no trip count to bound)
        stable = []
        outs = self._sub(body, body_consts + carry, f"{ctx}/while-w")
        for c, o in zip(carry, outs):
            stable.append(c if lat.join(c, o) == c else
                          lat.dtype_top(None))
        carry = stable
        self._sub(body, body_consts + carry, f"{ctx}/while-f")
    self._sub(cond, cond_consts + carry, f"{ctx}/while-c")
    for var, val in zip(eqn.outvars, carry):
        self._write(env, var, val)


def _scan(self: _Interp, env, eqn, ctx, ins):
    p = eqn.params
    nc, ncar = p["num_consts"], p["num_carry"]
    length = self.scale.lit(int(p.get("length", 1)))
    body = p["jaxpr"]
    consts = ins[:nc]
    carry = list(ins[nc:nc + ncar])
    xs = ins[nc + ncar:]
    ys_acc = None
    for it in range(_WHILE_JOIN_ITERS):
        outs = self._sub(body, consts + carry + xs, f"{ctx}/scan")
        new_carry = [lat.join(c, o) for c, o in zip(carry, outs[:ncar])]
        ys = outs[ncar:]
        ys_acc = ys if ys_acc is None else \
            [lat.join(a, y) for a, y in zip(ys_acc, ys)]
        if all(n == c for n, c in zip(new_carry, carry)):
            break
        carry = new_carry
    else:
        # linear widening: extrapolate the per-iteration drift over the
        # (symbolic) trip count — catches scan-accumulator overflow that
        # plain join-until-stable widening would lose.
        outs = self._sub(body, consts + carry + xs, f"{ctx}/scan-w")
        widened = []
        for c, o in zip(carry, outs[:ncar]):
            d_lo = o.lo - c.lo
            d_hi = o.hi - c.hi
            if (c.known and o.known and not math.isinf(d_lo)
                    and not math.isinf(d_hi)):
                widened.append(Ival(c.lo + min(d_lo, 0) * length,
                                    c.hi + max(d_hi, 0) * length, True))
            else:
                widened.append(lat.dtype_top(None))
        carry = widened
        outs = self._sub(body, consts + carry + xs, f"{ctx}/scan-f")
        ys_acc = [lat.join(a, y) for a, y in zip(ys_acc, outs[ncar:])]
    for var, val in zip(eqn.outvars, carry + (ys_acc or [])):
        self._write(env, var, val)
    # W1 on widened scan carries (the accumulator overflow check)
    self._check_w1(eqn, ctx, list(zip(eqn.invars[:nc + ncar],
                                      ins[:nc + ncar])),
                   list(zip(eqn.outvars[:ncar], carry)))


def _shard_map(self: _Interp, env, eqn, ctx, ins):
    p = eqn.params
    mesh = p.get("mesh")
    axes = {}
    if mesh is not None:
        names = getattr(mesh, "axis_names", ())
        try:
            sizes = dict(getattr(mesh, "shape", {}))
        except Exception:
            sizes = {}
        axes = {n: int(sizes.get(n, 1)) for n in names}
    self.mesh_stack.append(axes)
    try:
        inner = p.get("jaxpr")
        outs = self._sub(inner, ins, f"{ctx}/shard_map")
    finally:
        self.mesh_stack.pop()
    for var, val in zip(eqn.outvars, outs):
        self._write(env, var, val)


def _mesh_size(self: _Interp, axis_name) -> int:
    for frame in self.mesh_stack[::-1]:
        if axis_name in frame:
            return frame[axis_name]
    return 1


_Interp._mesh_size = _mesh_size


# ---------------------------------------------------------------------------
# Route audit (W3): permutation bijectivity + axis-name validity
# ---------------------------------------------------------------------------

def audit_routes(uses, name: str) -> list:
    """Check lifted collectives: ``ppermute`` tables must be partial
    permutations of the staged mesh axis (unique sources, unique
    destinations, ids in range); every named axis must be a mesh axis of
    the enclosing ``shard_map``. Returns W3 findings."""
    findings = []

    def emit(msg):
        findings.append(Finding(rule="W3-routes", path=f"<absint:{name}>",
                                line=0, message=msg))

    for use in uses:
        for a in use.axes:
            if use.mesh_axes and a not in use.mesh_axes:
                emit(f"{use.prim}: axis {a!r} is not an axis of the "
                     f"enclosing mesh {sorted(use.mesh_axes)}")
        if use.prim != "ppermute" or not use.perm:
            continue
        size = None
        if use.axes and use.mesh_axes:
            size = use.mesh_axes.get(use.axes[0])
        srcs = [s for s, _ in use.perm]
        dsts = [d for _, d in use.perm]
        if len(set(srcs)) != len(srcs):
            emit(f"ppermute: duplicate source in route table {use.perm} — "
                 f"not a partial permutation")
        if len(set(dsts)) != len(dsts):
            emit(f"ppermute: duplicate destination in route table "
                 f"{use.perm} — two shards would collide")
        if size is not None:
            bad = [x for x in srcs + dsts if not (0 <= x < size)]
            if bad:
                emit(f"ppermute: shard ids {sorted(set(bad))} outside the "
                     f"mesh axis {use.axes[0]!r} of size {size}")
    return findings


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def analyze_jaxpr(closed_jaxpr, *, name: str, scale: SymbolicScale,
                  input_ivals=None, rules=("W1", "W2", "W3")) -> AbsintReport:
    """Analyze a ClosedJaxpr under the symbolic scale. ``input_ivals``: one
    ``Ival`` (or None = unknown) per flat jaxpr input."""
    interp = _Interp(scale, name, rules)
    inner = closed_jaxpr.jaxpr
    consts = [interp._const_ival(c, v)
              for c, v in zip(closed_jaxpr.consts, inner.constvars)]
    n_in = len(inner.invars)
    args = list(input_ivals or [])[:n_in]
    args += [None] * (n_in - len(args))
    interp.run(inner, consts, args, "")
    findings = list(interp.findings.values())
    if "W3" in rules:
        findings += audit_routes(interp.report.collectives, name)
    interp.report.findings = findings
    return interp.report


def _flat_ivals(args, specs):
    """Per-argument interval specs → the jaxpr's flat input order. Each
    spec is None (every leaf unknown), one ``Ival`` (broadcast over the
    argument's leaves), or a structure-matching pytree of Ival/None."""
    import jax
    flat = []
    for a, s in zip(args, specs):
        n_leaves = len(jax.tree.leaves(a))
        if s is None or isinstance(s, Ival):
            flat += [s] * n_leaves
        else:
            leaves = jax.tree.leaves(
                s, is_leaf=lambda x: x is None or isinstance(x, Ival))
            assert len(leaves) == n_leaves, (len(leaves), n_leaves)
            flat += leaves
    return flat


def analyze(fn: Callable, args, *, name: str, scale: SymbolicScale,
            input_ivals=None, rules=("W1", "W2", "W3"),
            x64: bool = False) -> AbsintReport:
    """Trace ``fn(*args)`` (under x64 when asked — the widened-index
    configurations stage int64 programs) and analyze the closed jaxpr.
    ``input_ivals``: one spec per positional argument (see
    ``_flat_ivals``)."""
    import jax

    def trace():
        return jax.make_jaxpr(fn)(*args)

    if x64:
        with jax.enable_x64(True):
            closed = trace()
    else:
        closed = trace()
    flat = _flat_ivals(args, input_ivals) if input_ivals is not None else None
    return analyze_jaxpr(closed, name=name, scale=scale,
                         input_ivals=flat, rules=rules)
