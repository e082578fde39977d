"""Launch-layer tests: partition rules, input specs, shape rules, and the
loop-aware HLO analyzer (on canned HLO text — no compilation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.configs.shapes import SHAPES, applicable, cells
from repro.launch.hlo_stats import (analyze_hlo, multipliers,
                                    split_computations)
from repro.launch.specs import input_specs, batch_shard_specs, _kv_spec
from repro.models.zoo import build_model
from repro.sharding import param_specs

MESH_AXES = {"data": 16, "model": 16}


def test_shape_rules():
    # 8 full-attention archs skip long_500k; ssm+hybrid run it
    n_cells = 0
    for arch in ARCHS:
        cfg = get_config(arch)
        cs = cells(cfg)
        n_cells += len(cs)
        if cfg.family in ("ssm", "hybrid"):
            assert "long_500k" in cs, arch
        else:
            assert "long_500k" not in cs, arch
    assert n_cells == 32  # 40 - 8 documented skips


@pytest.mark.parametrize("arch", ["yi_6b", "olmoe_1b_7b", "mamba2_780m"])
def test_param_specs_divisibility(arch):
    """No spec may request a sharding that doesn't divide the dim."""
    cfg = get_config(arch, smoke=False)
    api = build_model(cfg)
    avals = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    specs = param_specs(avals, cfg, MESH_AXES, fsdp=True)
    for (path, leaf), (_, spec) in zip(
            jax.tree_util.tree_flatten_with_path(avals)[0],
            jax.tree_util.tree_flatten_with_path(specs)[0]):
        for dim, s in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            if s is None:
                continue
            axes = s if isinstance(s, tuple) else (s,)
            prod = 1
            for a in axes:
                prod *= MESH_AXES.get(a, 1)
            assert dim % prod == 0, (path, leaf.shape, spec)


def test_param_specs_shard_large_leaves():
    """Every >= 1M-element leaf must be sharded at least `model`-ways."""
    cfg = get_config("yi_6b")
    api = build_model(cfg)
    avals = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    specs = param_specs(avals, cfg, MESH_AXES, fsdp=True)
    for (path, leaf), (_, spec) in zip(
            jax.tree_util.tree_flatten_with_path(avals)[0],
            jax.tree_util.tree_flatten_with_path(specs)[0]):
        if leaf.size >= 1 << 20:
            assert any(s is not None for s in spec), (path, spec)


@pytest.mark.parametrize("arch", list(ARCHS))
def test_input_specs_cover_all_cells(arch):
    cfg = get_config(arch)
    for shape_name in cells(cfg):
        spec = SHAPES[shape_name]
        batch = input_specs(cfg, spec)
        assert batch["tokens"].dtype == jnp.int32
        if spec.kind == "decode":
            assert batch["tokens"].shape == (spec.global_batch, 1)
        else:
            assert batch["tokens"].shape == (spec.global_batch,
                                             spec.seq_len)
        bspecs = batch_shard_specs(batch, MESH_AXES)
        assert bspecs["tokens"][0] in ("data", ("pod", "data"), None)


def test_kv_spec_prefers_time_sharding():
    # (L, B, T, KH, hd): T=32768 divisible -> model on T
    s = _kv_spec((32, 128, 32768, 4, 128), MESH_AXES, 1)
    assert s[2] == "model" and s[1] == "data"
    # whisper cross-KV T=1500 not divisible -> falls back
    s = _kv_spec((6, 32, 1500, 8, 64), MESH_AXES, 1)
    assert s[2] is None and s[4] == "model" or s[3] == "model"


# ---------------------------------------------------------------------------
# HLO analyzer on canned text
# ---------------------------------------------------------------------------

_CANNED = """
HloModule jit_step

%body.1 (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]) parameter(0)
  %g = s32[] get-tuple-element(%p), index=0
  %x = f32[8,16]{1,0} get-tuple-element(%p), index=1
  %w = f32[16,32]{1,0} constant({...})
  %d = f32[8,32]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,32]{1,0} all-reduce(%d), channel_id=1, replica_groups=[16,16]<=[256], use_global_device_ids=true, to_apply=%add.1
  ROOT %t = (s32[], f32[8,16]) tuple(%g, %x)
}

%cond.1 (p: (s32[], f32[8,16])) -> pred[] {
  %p = (s32[], f32[8,16]) parameter(0)
  ROOT %lt = pred[] constant(true)
}

%add.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (arg: f32[8,16]) -> f32[8,16] {
  %arg = f32[8,16]{1,0} parameter(0)
  %init = (s32[], f32[8,16]) tuple(%c, %arg)
  %w = (s32[], f32[8,16]) while(%init), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %out = f32[8,16]{1,0} get-tuple-element(%w), index=1
}
"""


def test_hlo_stats_loop_aware():
    comps = split_computations(_CANNED)
    assert "body.1" in comps and "main" in comps
    mult = multipliers(_CANNED, comps)
    assert mult["main"] == 1.0
    assert mult["body.1"] == 10.0
    stats = analyze_hlo(_CANNED)
    # dot: 2 * (8*32) * 16 flops, x10 trips
    assert stats["flops"] == pytest.approx(10 * 2 * 8 * 32 * 16)
    colls = stats["collectives"]
    assert len(colls) == 1
    c = colls[0]
    assert c["op"] == "all-reduce" and c["group"] == 16
    # operand bytes = 8*32*4 x10; ring moved = 2*(15/16)*operand
    assert c["operand_bytes"] == pytest.approx(10 * 8 * 32 * 4)
    assert c["moved_bytes"] == pytest.approx(10 * 8 * 32 * 4 * 2 * 15 / 16)
    assert c["axis"] == "model"  # stride 1 groups


@pytest.mark.parametrize("env_dir", [None, "from_env"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` is the only directory used when set;
    otherwise the cache lives at the fixed ``<checkout>/.jax_cache``."""
    from pathlib import Path

    from repro.launch import compile_cache
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(Path(compile_cache.__file__).resolve().parents[3]
                   / ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        jax.config.update("jax_compilation_cache_dir", prev[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev[1])
