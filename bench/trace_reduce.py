"""Reduce a profiler trace (``.xplane.pb``) of one step to device metrics.

Reads the file with ``jax.profiler.ProfileData`` only. What it takes:

* the window: from the start of the first ``bench:<stage>`` host annotation
  to the end of the last (the harness wraps every stage of a step in one);
* per device plane ``/device:TPU:<i>``, the ``XLA Ops`` line: busy time is
  the union of the op intervals inside the window; an op's self time is its
  duration less that of the ops nested in it (a ``while`` holds its body);
* idle gaps: the stretches of the window in which no op runs, each named by
  the stage and the innermost host call on the Python thread at its middle.

Device and host clocks are not the same clock. Each device's offset is
taken as the median, over the programs it ran, of (end of the host's
``PJRT_LoadedExecutable_Execute``) - (start of the device's module), in
launch order; it places the window on the device's clock to within about
a millisecond, which matters only at the window's two ends.
"""
from __future__ import annotations

import re
import statistics

__all__ = ["op_kind", "reduce_file", "reduce_profile"]

_DEVICE = re.compile(r"/device:TPU:(\d+)")


def op_kind(name: str) -> tuple[str, str]:
    """(instruction, opcode) of an ``XLA Ops`` event name such as
    ``%fusion.3 = f32[8]{0} fusion(...)``; the name itself where it is not
    of that form."""
    m = re.match(r"%?([^ =]+) = ", name)
    if not m:
        return name, name
    rest = name[m.end():]
    if rest.startswith("("):          # tuple shape: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    op = re.match(r"\s*([A-Za-z][\w-]*)\(", rest)
    return m.group(1), (op.group(1) if op else m.group(1))


def _union(intervals):
    """Sorted, merged [start, end) list of (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _self_times(ops):
    """Self time per op event: duration less the nested ops' durations.
    ``ops``: list of (start, end, index). Returns {index: self_ns}."""
    out = {}
    stack = []
    for s, e, i in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        out[i] = e - s
        if stack and e <= stack[-1][1]:
            out[stack[-1][2]] -= e - s
        stack.append((s, e, i))
    return out


def reduce_profile(pd) -> dict:
    """The reduction of a loaded ``ProfileData``."""
    stages, execs, py = [], [], []
    devices = []
    for plane in pd.planes:
        m = _DEVICE.fullmatch(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [(e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events]
            devices.append({"id": int(m.group(1)), "ops": ops,
                            "modules": modules})
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    span = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if e.name.startswith("bench:"):
                        stages.append(span)
                    elif e.name == "PJRT_LoadedExecutable_Execute":
                        execs.append(span[1])
                    if line.name.startswith("python"):
                        py.append(span)
    if not stages:
        raise ValueError("the trace holds no bench:<stage> annotation")
    w0 = min(s for s, _, _ in stages)
    w1 = max(e for _, e, _ in stages)
    window_ns = w1 - w0
    execs.sort()

    def host_label(t):
        stage = next((n[len("bench:"):] for s, e, n in stages if s <= t < e),
                     "between stages")
        inner = [(e - s, n) for s, e, n in py
                 if s <= t < e and not n.startswith("bench:")]
        return f"{stage}: {min(inner)[1]}" if inner else stage

    kinds: dict[str, float] = {}
    instr: dict[str, float] = {}
    gaps = []
    per_device = []
    for d in devices:
        starts = sorted(s for s, _ in d["modules"])
        pairs = list(zip(execs, starts))
        offset = (statistics.median(h - s for h, s in pairs)
                  if pairs else 0.0)
        lo, hi = w0 - offset, w1 - offset
        spans = [(s, e) for s, e, _ in d["ops"]]
        busy = _union(_clip(spans, lo, hi))
        busy_ns = sum(e - s for s, e in busy)
        selfs = _self_times([(s, e, i) for i, (s, e, _) in
                             enumerate(d["ops"]) if e > lo and s < hi])
        for i, t in selfs.items():
            name = d["ops"][i][2]
            ins, kind = op_kind(name)
            kinds[kind] = kinds.get(kind, 0.0) + t / 1e9 / len(devices)
            key = f"{ins} ({kind})"
            instr[key] = instr.get(key, 0.0) + t / 1e9 / len(devices)
        edge = lo
        for s, e in busy + [(hi, hi)]:
            if s > edge:
                gaps.append((s - edge, edge + (s - edge) / 2 + offset))
            edge = max(edge, e)
        per_device.append({"id": d["id"], "busy_s": busy_ns / 1e9,
                           "offset_ns": offset})
    gaps = sorted(gaps, reverse=True)[:10]
    top = sorted(instr.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": (sum(d["busy_s"] for d in per_device) / len(per_device)
                   if per_device else 0.0),
        "devices": per_device,
        "op_kinds": kinds,
        "top_ops": [[k, v] for k, v in top],
        "idle_gaps": [[host_label(mid), ns / 1e9] for ns, mid in gaps],
    }


def reduce_file(path) -> dict:
    import jax

    return reduce_profile(jax.profiler.ProfileData.from_file(str(path)))
