#!/usr/bin/env python3
"""Benchmark harness for FOF halo finding on the chip, driven by data.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name, so a new cell or
metric is new files and entries, never an edit here:

* ``BENCHMARK.json`` (the checkout's root): the cell's configuration, traffic
  mix and chips, and the metrics;
* ``bench/configs/<config>.json``: the deployment, the name of its step
  module and the limit of every number the check compares;
* ``bench/traffic/<traffic>.json``: parameters for ``bench/generator.py``;
* ``bench/steps/<step>.py``: ``make(cfg, mix, chips, devices)`` returns the
  step (see ``bench/steps/fof_1chip.py`` for what it provides);
* ``bench/metrics/<metric>.py``: ``read(run) -> float | None`` for each
  per-layer metric, from the run record this module builds.

A run makes ``snapshots`` snapshots from ``(seed, k)``, places them on the
device and compiles the step's programs (all set-up); then it runs step i on
snapshot i mod K until the first step that ends after ``--seconds``. Every
stage of a step is fenced, traced or not. After the window it reads the
peak device memory, with ``--trace 1`` profiles one more step and runs the
step's own probes (those that read device time in a profiler trace of
their own), runs the step once more on a snapshot whose positions come
from the seed where the mix times a fixed pool, and then compares every
step's output with the plain reference (``bench/reference.py``). The last
line of standard output is one JSON object; the numbers compared and
their limits are also the last lines of standard error.
"""
from __future__ import annotations

import time

_T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from contextlib import contextmanager  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def process_age_s() -> float:
    """Seconds since this process started, from the kernel's record of its
    start; the time since this module was imported where that is missing."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        boot = time.clock_gettime(time.CLOCK_BOOTTIME)
        return boot - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return time.perf_counter() - _T_IMPORT


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("/", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of BENCHMARK.json with its files, found by name."""

    def __init__(self, root: pathlib.Path, name: str):
        self.root = pathlib.Path(root)
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {sorted(cells)}")
        self.workload = cells[name]
        self.name = name
        configs = {c["name"]: c for c in self.bench["configs"]}
        entry = configs[self.workload["config"]]
        self.cfg = json.loads((self.root / entry["file"]).read_text())
        self.chips = int(self.workload["chips"])
        bench_dir = self.root / "bench"
        self.mix = json.loads(
            (bench_dir / "traffic" / f"{self.workload['traffic']}.json")
            .read_text())
        self.step_path = bench_dir / "steps" / f"{self.cfg['step']}.py"
        self.metrics_dir = bench_dir / "metrics"

    def end_to_end(self) -> list[dict]:
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> list[dict]:
        reported = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if m["moves"] in reported
                and self.name in m.get("workloads", [self.name])]


def enable_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` where
    set, else ``<checkout>/.jax_cache``, a fixed path (it is part of each
    entry's key). Every program is cached, however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts programs lowered for compilation while ``active`` is set: a
    new program is lowered whether or not the compile cache then has it."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, _duration, **_kw):
        if self.active and event == self.EVENT:
            self.count += 1


@contextmanager
def _annotate(name: str):
    import jax

    with jax.profiler.TraceAnnotation(f"bench:{name}"):
        yield


class Stages:
    """Fenced stage spans of one step: ``stages(name, fn, *args)`` runs a
    compiled program, waits for its result and records its seconds."""

    def __init__(self):
        self.spans: dict[str, float] = {}

    def __call__(self, name, fn, *args):
        import jax

        with _annotate(name):
            t = time.perf_counter()
            out = jax.block_until_ready(fn(*args))
            self.spans[name] = self.spans.get(name, 0.0) + (
                time.perf_counter() - t)
        return out


def device_record(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def program_bytes(programs) -> int | None:
    """The largest arguments + outputs + temporaries of the compiled
    programs, per device, as the compiler lays them out. The runtime's
    ``peak_bytes_in_use`` leaves a running program's temporaries out on a
    TPU v5e, so this is what bounds the particles a chip can hold."""
    sizes = []
    for c in programs.values():
        m = c.memory_analysis()
        if m is None:
            return None
        sizes.append(m.argument_size_in_bytes + m.output_size_in_bytes
                     + m.temp_size_in_bytes)
    return max(sizes) if sizes else None


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return None if any(p is None for p in peaks) else max(peaks)


def _digest(host: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(host):
        h.update(k.encode())
        h.update(repr(host[k]).encode() if not hasattr(host[k], "tobytes")
                 else host[k].tobytes())
    return h.hexdigest()


def check_outputs(step, snaps, results, limits) -> tuple[dict, int]:
    """Compares every step's output with the reference; returns the worst
    reading of each number and how many steps failed one."""
    worst: dict[str, float] = {}
    failed = 0
    seen: dict[tuple, dict] = {}
    refs: dict[int, object] = {}
    for k, host in results:
        key = (k, _digest(host))
        if key not in seen:
            if k not in refs:
                refs[k] = step.reference(snaps[k])
            seen[key] = step.check(snaps[k], host, refs[k])
        vals = seen[key]
        for name, v in vals.items():
            worst[name] = max(worst.get(name, float("-inf")), float(v))
        failed += int(any(not float(v) <= limits[n] for n, v in vals.items()))
    return worst, failed


def _profiled(body):
    """``body()`` under the profiler; returns its result and the reduction
    of the trace (None where the profiler wrote none)."""
    import jax

    from bench import trace_reduce

    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host annotations are enough
        opts.enable_hlo_proto = False
        with jax.profiler.trace(tdir, profiler_options=opts):
            out = body()
        files = sorted(pathlib.Path(tdir).rglob("*.xplane.pb"))
        red = trace_reduce.reduce_file(files[-1]) if files else None
    return out, red


def _trace_step(step, placed):
    """One step under the profiler; returns its output, its spans, the
    reduction of the trace and the wall seconds with the profiler's own."""
    stages = Stages()
    t0 = time.perf_counter()
    out, red = _profiled(lambda: step.run(placed, stages))
    return out, stages.spans, red, time.perf_counter() - t0


def trace_call(name, fn, *args):
    """One fenced call of a compiled program under a profiler trace of its
    own: returns its output and the trace's reduction, whose ``busy_s`` is
    the program's device time."""
    return _profiled(lambda: Stages()(name, fn, *args))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices, log=print) -> dict:
    """Set-up, the measured window, the optional traced step and probes,
    and the comparison; returns the result object."""
    import jax

    from bench import generator

    step_mod = load_module(cell.step_path, cell.cfg["step"])
    step = step_mod.make(cell.cfg, cell.mix, cell.chips, devices)
    k_snaps = int(cell.mix["snapshots"])
    b = float(cell.cfg["fof_b"])
    snaps = [generator.snapshot(cell.mix, cell.chips, seed, k, b)
             for k in range(k_snaps)]
    placed = [step.place(s) for s in snaps]
    step.compile(placed[0])
    counter = CompileCounter()

    # --- the measured window -------------------------------------------
    outs, spans = [], []
    counter.active = True
    t_start = time.perf_counter()
    setup_s = process_age_s()
    i = 0
    while True:
        stages = Stages()
        outs.append((i % k_snaps, step.run(placed[i % k_snaps], stages)))
        spans.append(stages.spans)
        i += 1
        if time.perf_counter() - t_start >= seconds:
            break
    window_s = time.perf_counter() - t_start
    counter.active = False
    peak = peak_bytes(devices)
    step_bytes = program_bytes(step.programs)
    log(f"window: steps={i} window_s={window_s!r} setup_s={setup_s!r} "
        f"compiles_in_window={counter.count} peak_bytes_in_use={peak} "
        f"step_program_bytes={step_bytes} "
        f"step_s={[round(sum(s.values()), 4) for s in spans]} "
        f"stage_s={[{k: round(v, 4) for k, v in s.items()} for s in spans]}")

    run = {"n_per_device": step.particles_per_device, "steps": [],
           "window_s": window_s, "setup_s": setup_s, "probe": {},
           "trace": None, "devices": len(devices),
           "device_kind": devices[0].device_kind,
           "compiles_in_window": counter.count}
    for (k, out), sp in zip(outs, spans):
        run["steps"].append({"snapshot": k, "spans": sp, "counters": {
            c: int(v) for c, v in step.counters(out).items()}})

    if trace:
        k = i % k_snaps
        out, sp, red, wall = _trace_step(step, placed[k])
        outs.append((k, out))
        run["trace"] = red
        run["traced_step"] = {"snapshot": k, "spans": sp, "wall_s": wall}
        run["probe"] = step.probe(placed[0], outs[0][1], snaps[0],
                                  trace_call)
        log(f"traced step: wall_s={wall!r} spans={sp}")

    if "position_keys" in cell.mix:
        # The window times a fixed pool; positions drawn from the seed are
        # checked too, through the same programs at the same size.
        snaps.append(generator.snapshot(cell.mix, cell.chips, seed, k_snaps,
                                        b, pooled=False))
        stages = Stages()
        outs.append((k_snaps, step.run(step.place(snaps[-1]), stages)))
        log(f"seed-drawn snapshot: spans={stages.spans}")

    results = [(k, step.fetch(out)) for k, out in outs]
    del outs, placed
    step.release()

    limits = {n: float(v) for n, v in cell.cfg["limits"].items()}
    worst, failed = check_outputs(step, snaps, results, limits)
    correct = (bool(worst) and failed == 0
               and all(n in worst for n in limits))
    if trace:
        run["probe"].update(step.probe_reference(snaps[0]))

    metrics = {}
    if not trace:
        values = {
            "analysis_step_s": window_s / i,
            "setup_s": setup_s,
            "peak_bytes_per_particle": (
                None if step_bytes is None
                else step_bytes / step.particles_per_device),
        }
        for m in cell.end_to_end():
            v = values.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.per_layer():
            reader = load_module(cell.metrics_dir / f"{m['name']}.py",
                                 m["name"])
            v = reader.read(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    device = device_record(devices)
    device["memory_peak_bytes"] = peak
    result = {"correct": correct, "attempted": len(results),
              "failed": failed, "metrics": metrics, "device": device}
    if trace and run["trace"] is not None:
        red = run["trace"]
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["top_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = {n: {"value": worst.get(n), "limit": limits[n]}
                        for n in limits}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    if not (ROOT / "src" / "repro").is_dir():
        log("bench/run.py: the program (src/repro) is not in this checkout")
        return 2
    try:
        cell = Cell(ROOT, args.workload)
    except (KeyError, OSError, ValueError) as e:
        log(f"bench/run.py: {e}")
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    enable_compile_cache(ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench/run.py: JAX found no accelerator "
            f"(platform {devices[0].platform!r}); nothing measured")
        return 3
    if len(devices) < cell.chips:
        log(f"bench/run.py: the cell needs {cell.chips} chips, JAX found "
            f"{len(devices)}")
        return 3
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          devices[:cell.chips], log=log)
    except Exception:  # noqa: BLE001 — a failed run prints no result
        traceback.print_exc()
        return 1
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
