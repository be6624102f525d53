"""Sharding-rule unit tests (these don't need >1 device: PartitionSpec
construction is pure logic)."""
from __future__ import annotations

import numpy as np
import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.models.spec import TensorSpec
from repro.parallel import sharding as shd


@pytest.fixture(scope="module")
def meshes():
    # 1-device meshes can't test divisibility; build ABSTRACT meshes instead.
    single = AbstractMesh((16, 16), ("data", "model"))
    multi = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return single, multi


def test_fsdp_tp_param_layout(meshes):
    single, multi = meshes
    wq = TensorSpec((4096, 64, 128), ("embed", "heads", "qkv"))
    assert shd.pspec_for(wq, single) == P("data", "model", None)
    assert shd.pspec_for(wq, multi) == P(("pod", "data"), "model", None)


def test_divisibility_guard_drops_axis(meshes):
    single, _ = meshes
    # kv=1 (MQA): cannot shard 1 over 16 -> replicated
    wk = TensorSpec((4096, 1, 128), ("embed", "kv", "qkv"))
    assert shd.pspec_for(wk, single) == P("data", None, None)
    # kv=8 over model=16: not divisible -> dropped
    wk8 = TensorSpec((4096, 8, 128), ("embed", "kv", "qkv"))
    assert shd.pspec_for(wk8, single) == P("data", None, None)


def test_axis_tuple_prefix_fit(meshes):
    _, multi = meshes
    # embed rows divisible by pod(2) but not pod*data(32): prefix ("pod",)
    w = TensorSpec((2 * 7, 64), ("embed", "mlp"))
    assert shd.pspec_for(w, multi) == P("pod", "model")


def test_mesh_axis_used_once(meshes):
    single, _ = meshes
    # both dims want "model": second one must drop it
    w = TensorSpec((64, 128), ("heads", "mlp"))
    spec = shd.pspec_for(w, single)
    used = [e for e in spec if e is not None]
    assert len(used) == len(set(used)) == 1


def test_expert_sharding(meshes):
    single, _ = meshes
    wi = TensorSpec((64, 2048, 1408), ("experts", "embed", "mlp"))
    assert shd.pspec_for(wi, single) == P("model", "data", None)


def test_data_pspec(meshes):
    single, multi = meshes
    assert shd.data_pspec(single, 256, 2) == P("data", None)
    assert shd.data_pspec(multi, 256, 2) == P(("pod", "data"), None)
    # batch=1: not divisible -> replicated
    assert shd.data_pspec(multi, 1, 2) == P(None, None)


def test_cache_pspec_stacked_layout(meshes):
    single, _ = meshes
    # (G=21, B=128, S=32768, kv=8, hd=256): batch dim1 over data, seq/model
    spec = shd.cache_pspec(single, (21, 128, 32768, 8, 256), batch_dim=1)
    assert spec == P(None, "data", "model", None, None)
    # layer0 (B, S, kv, hd): batch dim0
    spec0 = shd.cache_pspec(single, (128, 32768, 16, 128), batch_dim=0)
    assert spec0 == P("data", "model", None, None)


def test_cache_pspec_b1_long_context(meshes):
    single, _ = meshes
    # long_500k: B=1 unshardable; seq must take "model"
    spec = shd.cache_pspec(single, (9, 1, 524288, 8, 128), batch_dim=1)
    assert spec == P(None, None, "model", None, None)


def test_score_pspec_choice(meshes):
    single, _ = meshes
    assert shd.default_score_pspec(single, 64) == P("data", "model", None, None)
    assert shd.default_score_pspec(single, 40) == P("data", None, "model", None)


def test_decode_score_pspec(meshes):
    single, _ = meshes
    assert shd.decode_score_pspec(single) == P("data", None, None, "model")


def test_param_pspecs_tree():
    from repro.configs import get_config
    from repro.models import lm
    mesh = AbstractMesh((16, 16), ("data", "model"))
    spec = lm.model_spec(get_config("gemma2-9b"))
    pspecs = shd.param_pspecs(spec, mesh)
    # embed (256000, 3584): vocab/model, embed/data
    assert pspecs["embed"] == P("model", "data")
    # every leaf produced a PartitionSpec
    assert all(isinstance(p, P) for p in jax.tree.leaves(
        pspecs, is_leaf=lambda x: isinstance(x, P)))
