"""Device time by library scope, from a profiler trace of one step and the
optimized HLO of the programs it ran.

The library runs each stage under a ``jax.named_scope`` with a dotted
``layer.stage`` name (``bvh.build``, ``dbscan.union``, ``halos.so_bisect``,
...). The compiler keeps that name in each instruction's ``op_name``
metadata, which the profiler's op events do not carry; this module joins the
two. ``reduce_scoped(pd, hlo_texts)`` returns what
``trace_reduce.reduce_profile(pd)`` returns, with these keys added:

* ``scopes``: {scope: device self seconds}, summed over every op whose
  ``op_name`` path holds that scope (a nested scope counts toward each of
  its ancestors too);
* ``scoped_share``: the share of the ops' self time that carries at least
  one library scope;
* ``modules``: {module: {"device_s", "scopes"}}, the same per program;
* ``top_ops``: the ten ops of most self time, each labelled
  ``<innermost scope>: <instruction> (<opcode>)`` where it has a scope.

How an op finds its scopes: each ``XLA Ops`` event belongs to the module
whose ``XLA Modules`` event holds its start (the module's name is the event's
name without ``(<id>)``); its instruction name is looked up in that module's
optimized HLO text (``compiled.as_text()`` of the program as compiled for the
chip that ran it); its scopes are the path components of its ``op_name``
that match ``^[a-z]+\\.[a-z_]+$``. A fusion without metadata takes that of
its fused computation's ROOT. An op whose metadata names no scope but that
runs nested in another op (inside a ``while``) takes the enclosing op's.
"""
from __future__ import annotations

import bisect
import re

from bench import trace_reduce

__all__ = ["SCOPE", "count_ops", "hlo_scopes", "hlo_texts", "module_name",
           "reduce_scoped"]

SCOPE = re.compile(r"^[a-z]+\.[a-z_]+$")

_HEADER = re.compile(r"^HloModule ([^\s,]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([^\s(]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([^\s,}]+)")
_EVENT_ID = re.compile(r"\(\d+\)$")


def module_name(hlo_text: str) -> str:
    """The module's name, from the ``HloModule <name>, ...`` header."""
    m = _HEADER.match(hlo_text)
    if not m:
        raise ValueError("not an HLO module's text")
    return m.group(1)


def hlo_texts(programs) -> dict[str, str]:
    """{module name: optimized HLO text} of compiled executables."""
    texts = [c.as_text() for c in programs]
    return {module_name(t): t for t in texts}


def hlo_scopes(hlo_text: str) -> dict[str, tuple[str, ...]]:
    """{instruction: its library scopes, outermost first} of one module."""
    own: dict[str, str | None] = {}
    calls: dict[str, str] = {}
    roots: dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(2)
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else None
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
        if m.group(1) and comp is not None:
            roots[comp] = name

    def op_name(name, depth=0):
        if own.get(name) is not None or depth > 8:
            return own.get(name) or ""
        root = roots.get(calls.get(name, ""))
        return op_name(root, depth + 1) if root else ""

    return {name: tuple(c for c in op_name(name).split("/") if SCOPE.match(c))
            for name in own}


def count_ops(hlo_text: str, opcode: str, scope: str) -> int:
    """Instructions of ``opcode`` whose scopes hold ``scope``: e.g. the tree
    builds of a program are its sorts under ``bvh.morton_sort``."""
    scopes = hlo_scopes(hlo_text)
    pattern = re.compile(rf"^\s+(?:ROOT )?%?([^\s=]+) = .*? {re.escape(opcode)}\(")
    return sum(1 for line in hlo_text.splitlines()
               if (m := pattern.match(line)) and scope in scopes[m.group(1)])


def _nesting(ops):
    """Self time and enclosing op of each op event. ``ops``: list of
    (start, end, index). Returns ({index: self_ns}, {index: parent or None})."""
    selfs, parents, stack = {}, {}, []
    for s, e, i in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        selfs[i] = e - s
        parents[i] = None
        if stack and e <= stack[-1][1]:
            parents[i] = stack[-1][2]
            selfs[stack[-1][2]] -= e - s
        stack.append((s, e, i))
    return selfs, parents


def reduce_scoped(pd, hlo_texts: dict[str, str]) -> dict:
    """``trace_reduce.reduce_profile(pd)`` with device time by scope; see
    the module's docstring. ``hlo_texts``: {module name: optimized HLO}."""
    red = trace_reduce.reduce_profile(pd)
    table = {mod: hlo_scopes(text) for mod, text in hlo_texts.items()}
    offsets = {d["id"]: d["offset_ns"] for d in red["devices"]}
    stages = [(e.start_ns, e.start_ns + e.duration_ns)
              for plane in pd.planes if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events
              if e.name.startswith("bench:")]
    w0 = min(s for s, _ in stages)
    w1 = max(e for _, e in stages)
    share = 1.0 / max(len(offsets), 1)

    scopes: dict[str, float] = {}
    modules: dict[str, dict] = {}
    labels: dict[str, float] = {}
    total = scoped = 0.0
    for plane in pd.planes:
        m = trace_reduce._DEVICE.fullmatch(plane.name)
        if not m:
            continue
        lo = w0 - offsets[int(m.group(1))]
        hi = w1 - offsets[int(m.group(1))]
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       _EVENT_ID.sub("", e.name))
                      for e in lines.get("XLA Modules", []))
        starts = [s for s, _, _ in mods]
        ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
               for e in lines.get("XLA Ops", [])]
        selfs, parents = _nesting([(s, e, i) for i, (s, e, _) in
                                   enumerate(ops) if e > lo and s < hi])
        own: dict[int, tuple[str, ...]] = {}
        module_of: dict[int, str | None] = {}
        for i in selfs:
            k = bisect.bisect_right(starts, ops[i][0]) - 1
            mod = mods[k][2] if k >= 0 and ops[i][0] < mods[k][1] else None
            module_of[i] = mod
            own[i] = table.get(mod, {}).get(trace_reduce.op_kind(ops[i][2])[0],
                                            ())

        def scopes_of(i):
            while i is not None and not own[i]:
                i = parents[i]
            return own[i] if i is not None else ()

        for i, t in selfs.items():
            sec = t / 1e9 * share
            path = tuple(dict.fromkeys(scopes_of(i)))
            ins, kind = trace_reduce.op_kind(ops[i][2])
            total += sec
            scoped += sec if path else 0.0
            label = f"{path[-1]}: {ins} ({kind})" if path else f"{ins} ({kind})"
            labels[label] = labels.get(label, 0.0) + sec
            for sc in path:
                scopes[sc] = scopes.get(sc, 0.0) + sec
            if module_of[i] is not None:
                entry = modules.setdefault(module_of[i],
                                           {"device_s": 0.0, "scopes": {}})
                entry["device_s"] += sec
                for sc in path:
                    entry["scopes"][sc] = entry["scopes"].get(sc, 0.0) + sec
    top = sorted(labels.items(), key=lambda kv: -kv[1])[:10]
    red.update(scopes=scopes, scoped_share=scoped / total if total else 0.0,
               modules=modules, top_ops=[[k, v] for k, v in top])
    return red
