#!/usr/bin/env python3
"""Device time of one cell's step by library scope, on the chip.

    python3 bench/scoped_step.py --workload <cell> --seed <n> [--steps 2]

Builds the cell as ``bench/run.py`` does (its configuration, traffic mix and
step module, found by name), compiles the step's programs, runs one step to
load them, times ``--steps`` more on snapshot 0 with the profiler off, and
then traces one step of the same snapshot and reduces the trace with
``bench/scopes.py`` against the optimized HLO of the step's programs. The
last line of standard output is one JSON object:

* ``untraced_step_s`` and ``traced_step_s``: fenced wall seconds of the
  step (the traced one with the profiler running);
* ``trace``: the scoped reduction (``scopes``, ``scoped_share``,
  ``modules``, ``top_ops``, ``busy_s``, ``window_s``, ...);
* ``tree_builds``: per program, its sorts under ``bvh.morton_sort`` in the
  compiled HLO, one per tree build;
* ``counters``: the step's program counters (union rounds).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=2)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench import generator, run, scopes

    cell = run.Cell(ROOT, args.workload)
    run.enable_compile_cache(ROOT)
    devices = jax.devices()[:cell.chips]
    if devices[0].platform != "tpu":
        print("bench/scoped_step.py: JAX found no accelerator; nothing "
              "measured", file=sys.stderr)
        return 3
    step = run.load_module(cell.step_path, cell.cfg["step"]).make(
        cell.cfg, cell.mix, cell.chips, devices)
    snap = generator.snapshot(cell.mix, cell.chips, args.seed, 0,
                              float(cell.cfg["fof_b"]))
    placed = step.place(snap)
    step.compile(placed)
    texts = scopes.hlo_texts(step.programs.values())
    step.run(placed, run.Stages())                 # loaded and warm

    untraced = []
    for _ in range(args.steps):
        stages = run.Stages()
        step.run(placed, stages)
        untraced.append(sum(stages.spans.values()))

    stages = run.Stages()
    with tempfile.TemporaryDirectory(prefix="scoped_step_") as tdir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        t0 = time.perf_counter()
        with jax.profiler.trace(tdir, profiler_options=opts):
            out = step.run(placed, stages)
        traced_wall = time.perf_counter() - t0
        path = sorted(pathlib.Path(tdir).rglob("*.xplane.pb"))[-1]
        red = scopes.reduce_scoped(
            jax.profiler.ProfileData.from_file(str(path)), texts)

    result = {
        "workload": cell.name, "seed": args.seed,
        "device": run.device_record(devices),
        "untraced_step_s": untraced,
        "traced_step_s": sum(stages.spans.values()),
        "traced_wall_s": traced_wall,
        "traced_spans": stages.spans,
        "counters": {k: int(v) for k, v in step.counters(out).items()},
        "tree_builds": {m: scopes.count_ops(t, "sort", "bvh.morton_sort")
                        for m, t in texts.items()},
        "trace": red,
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
