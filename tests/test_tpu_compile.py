"""Compile-only checks for one TPU v5e: the main path's Pallas kernels and
one FDBSCAN program compile for a described ``v5e:2x2`` (no chip attached)
with ``interpret=False``, at the sizes ``chip_smoke.py`` runs. Nothing
executes; what the chip's compiler would refuse fails here.

The topology is described inside a module fixture, never at import, so
every pytest-xdist worker collects the same tests and only the worker
given this file loads the TPU compiler.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import load_chip_smoke
from repro.core.dbscan import fdbscan
from repro.kernels import ops, segment

N_SMOKE = load_chip_smoke().DEFAULT_N
HBM_BYTES = 16e9           # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_native(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel
    return compiled


@pytest.mark.parametrize("d", [8, 1])
@pytest.mark.parametrize("kernel", ["segment_sum_sorted", "segment_max_sorted"])
def test_segment_kernels_compile(one_chip, kernel, d):
    fn = getattr(segment, kernel)
    _compile_native(
        lambda x, s: fn(x, s, 1 << 14, interpret=False),
        _spec(one_chip, (N_SMOKE, d)), _spec(one_chip, (N_SMOKE,), jnp.int32))


def test_eps_neighbor_counts_compiles(one_chip):
    _compile_native(
        lambda x: ops.eps_neighbor_counts(x, x, 0.01, interpret=False),
        _spec(one_chip, (4096, 3)))


def test_eps_min_label_compiles(one_chip):
    _compile_native(
        lambda x, lab, core: ops.eps_min_label(x, x, lab, core, 0.01,
                                               interpret=False),
        _spec(one_chip, (4096, 3)), _spec(one_chip, (4096,), jnp.int32),
        _spec(one_chip, (4096,), jnp.bool_))


@pytest.mark.parametrize("cap", [16, 64])
def test_cell_stencil_counts_compiles(one_chip, cap):
    ncells = 4096
    _compile_native(
        lambda p, nb: ops.cell_stencil_counts(p, nb, 0.01, interpret=False),
        _spec(one_chip, (ncells + 1, cap, 3)),
        _spec(one_chip, (ncells, 27), jnp.int32))


@pytest.mark.parametrize("cap", [16, 64])
def test_cell_stencil_min_label_compiles(one_chip, cap):
    ncells = 4096
    _compile_native(
        lambda p, lab, core, nb: ops.cell_stencil_min_label(
            p, lab, core, nb, 0.01, interpret=False),
        _spec(one_chip, (ncells + 1, cap, 3)),
        _spec(one_chip, (ncells + 1, cap), jnp.int32),
        _spec(one_chip, (ncells + 1, cap), jnp.bool_),
        _spec(one_chip, (ncells, 27), jnp.int32))


def test_fdbscan_compiles_within_hbm_at_smoke_n(one_chip):
    """FDBSCAN at 2^14 compiles; its temporaries per particle, reckoned at
    the smoke's n, stay under the chip's 16 GB."""
    n = 1 << 14
    compiled = fdbscan.lower(_spec(one_chip, (n, 3)),
                             _spec(one_chip, (), jnp.float32),
                             min_pts=2).compile()
    per_particle = compiled.memory_analysis().temp_size_in_bytes / n
    assert per_particle * N_SMOKE < HBM_BYTES, per_particle
