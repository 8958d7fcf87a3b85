"""Launcher tests: specs, census parsing, link model, sharding modes,
hints, and a real (subprocess) dry-run integration check."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, SHAPES, cells_for, get_config
from repro.launch.hlo_census import (
    _group_size,
    _link_bytes,
    collective_census,
    parse_computations,
)
from repro.launch.specs import (
    batch_specs,
    cache_specs,
    make_train_step,
    params_specs,
)
from repro.models.model import build_model

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_cells_for_skip_policy():
    assert "long_500k" in cells_for("mamba2-2.7b")
    assert "long_500k" in cells_for("gemma3-12b")
    assert "long_500k" not in cells_for("qwen3-32b")
    total = sum(len(cells_for(a)) for a in ARCH_IDS)
    assert total == 33  # 40 assigned minus 7 documented skips


@pytest.mark.parametrize("arch", ["qwen2-vl-72b", "whisper-medium",
                                  "jamba-v0.1-52b", "qwen3-32b"])
def test_batch_specs_shapes(arch):
    cfg = get_config(arch)
    sp = batch_specs(cfg, SHAPES["train_4k"])
    b, s = 256, 4096
    if cfg.family == "vlm":
        assert sp["embeddings"].shape == (b, s, cfg.d_model)
        assert sp["positions"].shape == (3, b, s)
    elif cfg.is_encoder_decoder:
        assert sp["frames"].shape == (b, s, cfg.d_model)
        assert sp["tokens"].shape == (b, s)
    else:
        assert sp["tokens"].shape == (b, s)
    assert sp["labels"].shape == (b, s)


def test_cache_specs_no_allocation():
    cfg = get_config("qwen3-32b")
    model = build_model(cfg)
    cs = cache_specs(model, SHAPES["decode_32k"])
    leaves = jax.tree_util.tree_leaves(cs)
    assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
    k = cs["k"]
    assert k.shape == (cfg.num_layers, 128, 32768, cfg.num_kv_heads,
                       cfg.resolved_head_dim)


_FAKE_HLO = """
HloModule test

%cond.1 (arg.1: (s32[], f32[64])) -> pred[] {
  %arg.1 = (s32[], f32[64]) parameter(0)
  %gte = s32[] get-tuple-element(%arg.1), index=0
  %c28 = s32[] constant(28)
  ROOT %cmp = pred[] compare(%gte, %c28), direction=LT
}

%body.2 (arg.2: (s32[], f32[64])) -> (s32[], f32[64]) {
  %arg.2 = (s32[], f32[64]) parameter(0)
  %gte2 = f32[64]{0} get-tuple-element(%arg.2), index=1
  %ag = f32[1024]{0} all-gather(%gte2), channel_id=1, replica_groups=[16,16]<=[256], dimensions={0}
  %red = f32[64]{0} bitcast(%ag)
  %ar = f32[64]{0} all-reduce(%red), channel_id=2, replica_groups=[16,16]<=[256], to_apply=%cond.1
  %i = s32[] get-tuple-element(%arg.2), index=0
  ROOT %tup = (s32[], f32[64]) tuple(%i, %ar)
}

ENTRY %main (p0: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %ar0 = f32[64]{0} all-reduce(%p0), channel_id=3, replica_groups=[16,16]<=[256], to_apply=%cond.1
  %init = (s32[], f32[64]) tuple(%p0, %ar0)
  %wh = (s32[], f32[64]) while(%init), condition=%cond.1, body=%body.2
  ROOT %out = f32[64]{0} get-tuple-element(%wh), index=1
}
"""


def test_census_trip_count_weighting():
    c = collective_census(_FAKE_HLO)
    # all-gather + all-reduce inside the 28-trip loop, one AR outside
    assert c["counts"]["all-gather"] == 1
    assert c["counts"]["all-reduce"] == 2
    assert c["weighted_counts"]["all-gather"] == 28
    assert c["weighted_counts"]["all-reduce"] == 28 + 1
    # operand bytes: 64 f32 = 256 B; AG weighted 28x
    assert c["bytes_per_device"]["all-gather"] == 28 * 256
    assert c["bytes_per_device"]["all-reduce"] == 29 * 256


def test_census_parses_computations():
    comps = parse_computations(_FAKE_HLO)
    assert any(c["is_entry"] for c in comps.values())
    ent = [c for c in comps.values() if c["is_entry"]][0]
    assert ent["whiles"] == [("cond.1", "body.2")]


def test_link_model():
    assert _link_bytes("all-gather", 100, 16) == 1500  # shard x (g-1)
    assert _link_bytes("all-reduce", 100, 16) == pytest.approx(187.5)
    assert _link_bytes("reduce-scatter", 100, 16) == pytest.approx(93.75)
    assert _link_bytes("collective-permute", 100, 2) == 100
    assert _link_bytes("all-reduce", 100, 1) == 0
    assert _group_size("all-reduce(%x), replica_groups=[32,8]<=[256]") == 8


def test_param_spec_serve_mode():
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_abstract_mesh
    from repro.launch.sharding import param_spec

    mesh = make_abstract_mesh((16, 16), ("data", "model"))
    spec = param_spec(mesh, "layers/attn/wq", (64, 5120, 8192),
                      serve_mode=True)
    assert spec == P(None, None, "model")  # no FSDP axes at decode


def test_shard_hint_noop_without_mesh():
    from repro.models.hints import hint_batch, shard_hint

    x = jnp.ones((4, 8))
    assert shard_hint(x, "data") is x or (shard_hint(x, "data") == x).all()
    assert (hint_batch(jnp.ones((2, 3, 4))) == 1).all()


def test_train_steps_lower_on_host_mesh():
    """train/serve steps lower under the degenerate host mesh (the same
    code path production uses, minus fake devices)."""
    from repro.launch.mesh import make_host_mesh

    cfg = get_config("qwen2-1.5b", smoke=True)
    model = build_model(cfg)
    mesh = make_host_mesh()
    step = make_train_step(model)
    p = params_specs(model)
    from repro.launch.specs import make_opt_specs

    o = make_opt_specs(model)
    b = {"tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32),
         "labels": jax.ShapeDtypeStruct((4, 32), jnp.int32)}
    with jax.set_mesh(mesh):
        lowered = jax.jit(step).lower(p, o, b)
        assert lowered.cost_analysis().get("flops", 0) > 0


@pytest.mark.slow
def test_dryrun_subprocess_smallest_cell():
    """End-to-end integration: the real dry-run binary on the cheapest
    cell (mamba2 long_500k: B=1, compiles in seconds)."""
    out = "/tmp/test_dryrun_cell.json"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "mamba2-2.7b", "--shape", "long_500k", "--mesh", "single",
         "--no-probe", "--out", out],
        env={**os.environ, "PYTHONPATH": SRC},
        capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    data = json.load(open(out))
    assert len(data["results"]) == 1 and not data["failures"]
    cell = data["results"][0]
    assert cell["memory"]["peak_bytes"] < 16e9  # fits v5e HBM


def test_compile_cache_follows_env_else_fixed_repo_path(monkeypatch):
    from repro.launch import compile_cache

    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    assert str(compile_cache.REPO_CACHE_DIR) == os.path.join(repo, ".jax_cache")
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        # a set variable is JAX's own setting: no other directory is set
        jax.config.update("jax_compilation_cache_dir", saved[0])
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == saved[0]
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          saved[1])
