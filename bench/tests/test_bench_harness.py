"""The harness: found by name, refuses to run without a chip, and decides
`correct` against the reference even when the timed path is broken.
The cells hold one chip, so no fault of an exchange between chips
applies.

Runs on the CPU at small sizes: ``run_cell`` is called directly, past
``main``'s look for a TPU."""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run

ROOT = pathlib.Path(run.__file__).resolve().parents[1]


def _small(cell, n=512, snapshots=2):
    cell.mix["particles_per_chip"] = n
    cell.mix["snapshots"] = snapshots
    if "position_keys" in cell.mix:
        cell.mix["position_keys"] = cell.mix["position_keys"][:snapshots]
    return cell


def _quiet(_msg):
    pass


# --- discovery: a new cell and metric are files and entries only ----------

TOY_STEP = '''
import jax.numpy as jnp
import numpy as np


class Toy:
    def __init__(self, cfg, mix, chips, devices):
        self.particles_per_device = mix["particles_per_chip"]
        self.programs = {}

    def place(self, snap):
        return jnp.asarray(snap.points)

    def compile(self, placed):
        import jax
        self.programs["sum"] = jax.jit(lambda p: jnp.sum(p, axis=0)).lower(
            placed).compile()

    def run(self, placed, stages):
        return stages("sum", self.programs["sum"], placed)

    def counters(self, out):
        return {}

    def fetch(self, out):
        return {"sum": np.asarray(out)}

    def release(self):
        self.programs.clear()

    def reference(self, snap):
        return snap.points.astype(np.float64).sum(axis=0)

    def check(self, snap, host, ref):
        return {"toy_err": float(np.abs(host["sum"] - ref).max())}

    def probe(self, placed, out, snap, trace_call):
        again, red = trace_call("toy", self.programs["sum"], placed)
        return {"toy": 7.0, "traced_equal": bool((again == out).all()),
                "traced_window_s": red["window_s"]}

    def probe_reference(self, snap):
        return {}


def make(cfg, mix, chips, devices):
    return Toy(cfg, mix, chips, devices)
'''


def test_new_cell_and_metric_are_found_by_name(tmp_path):
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "toy", "source": "https://example.org/toy",
                     "file": "bench/configs/toy.json", "reduced": [],
                     "why": "a toy"}],
        "workloads": [{"name": "toy.cell", "config": "toy",
                       "traffic": "toy_mix", "chips": 1, "why": "a toy"}],
        "end_to_end": [
            {"name": "analysis_step_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "toy.probe", "unit": "1", "better": "higher",
             "source": "program_counter", "layer": "toy",
             "moves": "analysis_step_s", "workloads": ["toy.cell"]},
            {"name": "toy.silent", "unit": "1", "better": "higher",
             "source": "program_counter", "layer": "toy",
             "moves": "analysis_step_s"}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for sub in ("configs", "traffic", "steps", "metrics"):
        (tmp_path / "bench" / sub).mkdir(parents=True)
    (tmp_path / "bench/configs/toy.json").write_text(json.dumps(
        {"step": "toy_step", "fof_b": 0.168, "limits": {"toy_err": 1e-3}}))
    (tmp_path / "bench/traffic/toy_mix.json").write_text(json.dumps(
        {"particles_per_chip": 256, "snapshots": 3, "halos": 0,
         "background_share": 1.0}))
    (tmp_path / "bench/steps/toy_step.py").write_text(TOY_STEP)
    (tmp_path / "bench/metrics/toy.probe.py").write_text(
        "def read(run):\n    return run['probe']['toy'] + len(run['steps'])\n")
    (tmp_path / "bench/metrics/toy.silent.py").write_text(
        "def read(run):\n    return None\n")

    cell = run.Cell(tmp_path, "toy.cell")
    res = run.run_cell(cell, 2**31 + 99, 0.05, False, jax.devices()[:1],
                       log=_quiet)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"analysis_step_s", "setup_s"}
    assert res["checks"]["toy_err"]["limit"] == 1e-3
    assert list(res)[-1] == "checks"

    res = run.run_cell(cell, 5, 0.05, True, jax.devices()[:1], log=_quiet)
    assert res["correct"] is True
    assert res["metrics"]["toy.probe"]["value"] >= 8.0
    assert "toy.silent" not in res["metrics"]  # a reader with nothing to read
    assert res["device"]["window_s"] > 0
    # A mix without a position pool checks the window's steps and the
    # traced one, and runs no snapshot drawn apart from them.
    assert res["attempted"] == res["metrics"]["toy.probe"]["value"] - 7 + 1


def test_trace_call_profiles_one_program():
    """A probe's program runs once under a profiler trace of its own; the
    reduction's window is that one call."""
    prog = jax.jit(lambda x: jnp.sin(x) @ x.T).lower(
        jnp.ones((64, 64))).compile()
    out, red = run.trace_call("one", prog, jnp.ones((64, 64)))
    np.testing.assert_allclose(out, prog(jnp.ones((64, 64))))
    assert red is not None and red["window_s"] > 0


def test_cells_of_the_benchmark_resolve():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = run.Cell(ROOT, w["name"])
        assert cell.step_path.is_file()
        assert set(cell.cfg["limits"])
        for m in cell.per_layer():
            assert (cell.metrics_dir / f"{m['name']}.py").is_file()
        names = {m["name"] for m in cell.end_to_end()}
        assert {"setup_s", "analysis_step_s"} <= names


def _bench_cmd(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fof_clustered",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_accelerator_exits_nonzero_without_a_result():
    p = _bench_cmd(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench_cmd(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# --- correct comes out false when the timed path is broken ------------------

def _run_broken(monkeypatch, cell, wrap, seconds=0.3):
    real = run.load_module

    def load(path, name):
        mod = real(path, name)
        if pathlib.Path(path) == cell.step_path:
            return types.SimpleNamespace(
                make=lambda *a: wrap(mod.make(*a)))
        return mod

    monkeypatch.setattr(run, "load_module", load)
    return run.run_cell(cell, 2**31 + 17, seconds, False,
                        jax.devices()[:1], log=_quiet)


@pytest.fixture(scope="module")
def sound():
    """A sound run of the one-chip step at a small size."""
    cell = _small(run.Cell(ROOT, "fof_clustered"))
    return run.run_cell(cell, 2**31 + 17, 0.3, False, jax.devices()[:1],
                        log=_quiet)


def test_sound_run_is_correct(sound):
    assert sound["correct"] is True, sound["checks"]
    assert sound["failed"] == 0 and sound["attempted"] >= 2


def test_seed_drawn_snapshot_is_checked(monkeypatch):
    """The window times a fixed pool; a step that is wrong only on
    positions outside the pool is still caught, by the snapshot drawn
    from the seed after the window."""
    from bench import generator

    cell = _small(run.Cell(ROOT, "fof_clustered"))
    pool = {generator.snapshot(cell.mix, 1, 2**31 + 17, k, 0.168)
            .points.tobytes() for k in range(cell.mix["snapshots"])}
    fresh = set()

    def wrap(step):
        real_place, real_run = step.place, step.run

        def place(snap):
            placed = real_place(snap)
            if snap.points.tobytes() not in pool:
                fresh.add(id(placed))
            return placed

        def broken(placed, stages):
            res, cat, props = real_run(placed, stages)
            if id(placed) in fresh:
                lab = res.labels
                i = int(jnp.argmax(lab >= 0))
                res = res._replace(labels=lab.at[i].set(-1))
            return res, cat, props
        step.place, step.run = place, broken
        return step

    res = _run_broken(monkeypatch, cell, wrap)
    assert fresh, "no snapshot outside the pool was run"
    assert res["correct"] is False and res["failed"] == 1
    assert res["checks"]["fof_mismatch"]["value"] >= 1


def test_altered_answer_is_caught(monkeypatch):
    cell = _small(run.Cell(ROOT, "fof_clustered"))

    def wrap(step):
        real_run = step.run

        def broken(placed, stages):
            res, cat, props = real_run(placed, stages)
            lab = res.labels
            i = int(jnp.argmax(lab >= 0))
            return res._replace(labels=lab.at[i].set(-1)), cat, props
        step.run = broken
        return step

    res = _run_broken(monkeypatch, cell, wrap)
    assert res["correct"] is False
    assert res["checks"]["fof_mismatch"]["value"] >= 1


def test_altered_catalog_is_caught(monkeypatch):
    """A halo's member count altered where the catalog is produced: the
    labels stay right, so only the catalog's own numbers can catch it."""
    cell = _small(run.Cell(ROOT, "fof_clustered"))

    def wrap(step):
        real_run = step.run

        def broken(placed, stages):
            res, cat, props = real_run(placed, stages)
            return res, cat._replace(count=cat.count.at[0].add(1)), props
        step.run = broken
        return step

    res = _run_broken(monkeypatch, cell, wrap)
    assert res["correct"] is False
    assert res["checks"]["fof_mismatch"]["value"] == 0
    assert res["checks"]["catalog_mismatch"]["value"] >= 1


def test_half_the_particles_left_out_is_caught(monkeypatch):
    cell = _small(run.Cell(ROOT, "fof_clustered"))

    def wrap(step):
        real_compile = step.compile

        def compile_half(placed):
            from repro.core.dbscan import fdbscan

            real_compile(placed)

            def half(points, eps):
                """FOF over the first half of the particles; the rest are
                left out."""
                n = points.shape[0]
                res = fdbscan(points[:n // 2], eps, step.min_pts)
                return res._replace(labels=jnp.concatenate(
                    [res.labels, jnp.full((n - n // 2,), -1, jnp.int32)]),
                    core_mask=jnp.concatenate(
                        [res.core_mask, jnp.zeros((n - n // 2,), bool)]))
            step.programs["fdbscan"] = jax.jit(half).lower(
                placed[0], placed[2]).compile()
        step.compile = compile_half
        return step

    res = _run_broken(monkeypatch, cell, wrap)
    assert res["correct"] is False
    assert res["checks"]["fof_mismatch"]["value"] > 10


def test_stale_step_is_caught(monkeypatch):
    """A step that hands back what it returned before, whatever the
    snapshot."""
    cell = _small(run.Cell(ROOT, "fof_clustered"))

    def wrap(step):
        real_run = step.run
        first = []

        def stale(placed, stages):
            out = real_run(placed, stages)
            if not first:
                first.append(out)
            return first[0]
        step.run = stale
        return step

    res = _run_broken(monkeypatch, cell, wrap)
    assert res["correct"] is False
    assert res["failed"] >= 1


def test_control_in_lower_precision_fails():
    """The reference on bfloat16 particles, in the program's place, must
    come out not correct in every cell."""
    from bench import generator

    for name, n in (("fof_clustered", 2048), ("fof_uniform", 4096)):
        cell = run.Cell(ROOT, name)
        cell.mix["particles_per_chip"] = n
        step = run.load_module(cell.step_path, cell.cfg["step"]).make(
            cell.cfg, cell.mix, cell.chips, jax.devices()[:1])
        limits = cell.cfg["limits"]
        for seed in (1, 2**31 + 3):
            snap = generator.snapshot(cell.mix, cell.chips, seed, 0,
                                      float(cell.cfg["fof_b"]))
            vals = step.check(snap, step.control(snap), step.reference(snap))
            assert any(v > limits[k] for k, v in vals.items()), (name, vals)
