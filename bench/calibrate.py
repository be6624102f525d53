#!/usr/bin/env python3
"""Readings from which each limit of `correct` is set; not part of a run.

    python3 bench/calibrate.py --workload <cell> --seeds 100-111 \
        [--control-seeds 100-102] [--out readings.json]

In one process: set the cell up once, then for each program seed make its
snapshots with positions drawn from the seed (never a mix's fixed pool),
run the timed step on each (the same compiled programs a run's window
drives, at the cell's size) and compare with the reference; for each
control seed, put the reference computed on bfloat16 particles (the
precision below the configuration's float32) in the program's place and
compare it the same way. Prints each seed's worst reading of every number,
one JSON object per line, and the lower reading (largest of the program's)
and upper reading (smallest of the control's) of each number.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def _seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out.extend(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def readings(cell, seeds, control_seeds, devices, log=print) -> dict:
    from bench import generator
    from bench.run import Stages, load_module

    step = load_module(cell.step_path, cell.cfg["step"]).make(
        cell.cfg, cell.mix, cell.chips, devices)
    k_snaps = int(cell.mix["snapshots"])
    b = float(cell.cfg["fof_b"])
    compiled = False
    program, control = {}, {}
    for seed in seeds:
        worst = {}
        for k in range(k_snaps):
            snap = generator.snapshot(cell.mix, cell.chips, seed, k, b,
                                      pooled=False)
            placed = step.place(snap)
            if not compiled:
                step.compile(placed)
                compiled = True
            stages = Stages()
            host = step.fetch(step.run(placed, stages))
            vals = step.check(snap, host, step.reference(snap))
            for n, v in vals.items():
                worst[n] = max(worst.get(n, float("-inf")), float(v))
            worst.setdefault("step_s", 0.0)
            worst["step_s"] = max(worst["step_s"], sum(stages.spans.values()))
            worst.setdefault("rounds", 0)
            worst["rounds"] = max(worst["rounds"], host["num_rounds"])
        program[seed] = worst
        log(json.dumps({"program_seed": seed, **worst}))
    for seed in control_seeds:
        worst = {}
        for k in range(k_snaps):
            snap = generator.snapshot(cell.mix, cell.chips, seed, k, b,
                                      pooled=False)
            vals = step.check(snap, step.control(snap), step.reference(snap))
            for n, v in vals.items():
                worst[n] = max(worst.get(n, float("-inf")), float(v))
        control[seed] = worst
        log(json.dumps({"control_seed": seed, **worst}))
    names = list(cell.cfg["limits"])
    summary = {n: {"lower": max((w[n] for w in program.values()),
                                default=None),
                   "upper": min((w[n] for w in control.values()),
                                default=None),
                   "limit": cell.cfg["limits"][n]} for n in names}
    return {"program": program, "control": control, "summary": summary}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench.run import Cell, enable_compile_cache

    enable_compile_cache(ROOT)
    cell = Cell(ROOT, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"calibrate.py: needs {cell.chips} TPU chips, found "
              f"{len(devices)} {devices[0].platform}", file=sys.stderr)
        return 3
    t = time.perf_counter()
    out = readings(cell, _seeds(args.seeds), _seeds(args.control_seeds),
                   devices[:cell.chips])
    out["seconds"] = time.perf_counter() - t
    print(json.dumps(out["summary"]))
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
