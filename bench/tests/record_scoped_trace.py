#!/usr/bin/env python3
"""Records the trace that ``test_bench_scopes.py`` reads, on one TPU v5e.

    python3 bench/tests/record_scoped_trace.py [out_dir]

The program: a 256 x 256 matmul under the scope ``tiny.matmul``, then a
20-step while loop whose body runs under ``tiny.body``, then an unscoped
``tanh`` of the transpose. It runs three times, each inside a
``bench:scoped<k>`` host annotation, with 20 ms host sleeps between them,
under the profiler as the benchmark harness traces a step. Writes
``scoped_v5e.xplane.pb`` and the program's optimized HLO,
``scoped_v5e.hlo.txt``, to ``out_dir`` (default: ``bench/tests/data``).
"""
from __future__ import annotations

import pathlib
import shutil
import sys
import tempfile
import time


def scoped(x):
    import jax
    import jax.numpy as jnp

    with jax.named_scope("tiny.matmul"):
        y = x @ x

    def body(carry):
        i, acc = carry
        with jax.named_scope("tiny.body"):
            acc = jnp.sin(acc) * 0.5 + jnp.sum(acc) * 1e-6
        return i + 1, acc

    _, y = jax.lax.while_loop(lambda c: c[0] < 20, body, (0, y))
    return jnp.tanh(y.T)


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp

    argv = sys.argv[1:] if argv is None else argv
    out = pathlib.Path(argv[0] if argv else
                       pathlib.Path(__file__).with_name("data"))
    out.mkdir(parents=True, exist_ok=True)
    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace.py: needs a TPU", file=sys.stderr)
        return 3
    x = jnp.linspace(0.0, 1.0, 256 * 256, dtype=jnp.float32).reshape(256, 256)
    compiled = jax.jit(scoped).lower(x).compile()
    jax.block_until_ready(compiled(x))
    (out / "scoped_v5e.hlo.txt").write_text(compiled.as_text())
    with tempfile.TemporaryDirectory(prefix="scoped_trace_") as tdir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        with jax.profiler.trace(tdir, profiler_options=opts):
            for k in range(3):
                with jax.profiler.TraceAnnotation(f"bench:scoped{k}"):
                    jax.block_until_ready(compiled(x))
                time.sleep(0.02)
        files = sorted(pathlib.Path(tdir).rglob("*.xplane.pb"))
        shutil.copy(files[-1], out / "scoped_v5e.xplane.pb")
    print(f"wrote {out / 'scoped_v5e.xplane.pb'} and "
          f"{out / 'scoped_v5e.hlo.txt'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
