"""Dry-run planning logic (no compilation, abstract meshes)."""
from __future__ import annotations

import numpy as np
import jax
import pytest
from jax.sharding import AbstractMesh

# NOTE: importing repro.launch.dryrun sets XLA_FLAGS; harmless here because
# jax is already initialized with 1 device by the time tests import it.
from repro.configs import ARCH_IDS, SHAPES, get_config, shapes_for
from repro.launch import dryrun as dr


@pytest.fixture(scope="module")
def mesh():
    return AbstractMesh((16, 16), ("data", "model"))


@pytest.fixture(scope="module")
def multi_mesh():
    return AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_input_specs_shapes():
    cfg = get_config("gemma2-9b")
    b = dr.input_specs(cfg, SHAPES["train_4k"])
    assert b["tokens"].shape == (256, 4096)
    assert b["labels"].shape == (256, 4096)
    d = dr.input_specs(cfg, SHAPES["decode_32k"])
    assert d["tokens"].shape == (128, 1)


def test_input_specs_modality_stubs():
    vlm = dr.input_specs(get_config("llama-3.2-vision-11b"), SHAPES["train_4k"])
    assert vlm["vision"].shape == (256, 1601, 7680)
    aud = dr.input_specs(get_config("seamless-m4t-large-v2"), SHAPES["train_4k"])
    assert aud["frames"].shape == (256, 1024, 1024)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_plan_state_fits(arch, mesh):
    """Every arch's training state (params+opt+grads) must fit the plan."""
    cfg = get_config(arch)
    plan = dr.train_plan(cfg, SHAPES["train_4k"], mesh)
    params_b = plan["params_b"]
    grad_mult = 1 if plan["grad_dtype"] == "bfloat16" else 2
    state = params_b * (3 + grad_mult)
    assert state < 15e9, (arch, state / 1e9)
    assert plan["accum"] >= 1
    assert plan["rows"] * plan["accum"] * 16 == SHAPES["train_4k"].global_batch


def test_jamba_uses_bf16_grads(mesh):
    plan = dr.train_plan(get_config("jamba-1.5-large-398b"),
                         SHAPES["train_4k"], mesh)
    assert plan["grad_dtype"] == "bfloat16"


def test_small_models_keep_f32_grads(mesh):
    plan = dr.train_plan(get_config("gemma2-9b"), SHAPES["train_4k"], mesh)
    assert plan["grad_dtype"] == "float32"


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("mesh_kind", ["single", "multi"])
def test_memory_model_all_cells_fit(arch, mesh_kind, mesh, multi_mesh):
    m = mesh if mesh_kind == "single" else multi_mesh
    cfg = get_config(arch)
    for shape in shapes_for(cfg):
        mm = dr.memory_model(cfg, shape, m)
        assert mm["fits_16GB"], (arch, shape.name, mesh_kind,
                                 {k: round(v / 1e9, 2) for k, v in mm.items()
                                  if isinstance(v, float)})


def test_shapes_for_rules():
    assert len(shapes_for(get_config("gemma2-9b"))) == 3      # no long_500k
    assert len(shapes_for(get_config("jamba-1.5-large-398b"))) == 4


def test_model_flops_moe_uses_active_params(mesh):
    dense = dr.model_flops(get_config("gemma2-9b"), SHAPES["train_4k"])
    # 6 * 9.24e9 * 256*4096 within 1%
    assert abs(dense - 6 * 9.242e9 * 256 * 4096) / dense < 0.01
    moe = dr.model_flops(get_config("qwen3-moe-235b-a22b"), SHAPES["train_4k"])
    # active ~22B, not 235B
    assert moe < 6 * 40e9 * 256 * 4096


def test_collective_bytes_parser():
    hlo = """
      %ag = bf16[64,128]{1,0} all-gather(%x), replica_groups=[2,4]<=[8]
      %ar = f32[16]{0} all-reduce(%y), to_apply=%add
    """
    out = dr.collective_bytes(hlo)
    assert out["all-gather"] == 64 * 128 * 2
    assert out["all-reduce"] == 16 * 4 * 2
