"""Launch-layer unit tests: override parsing, optimized presets, step specs,
mesh construction (logical), and the roofline report recompute path."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_shape
from repro.launch.dryrun import OPTIMIZED_PRESETS, apply_overrides, parse_overrides


def test_parse_overrides_types():
    ov = parse_overrides(["attn_chunk=2048", "moe.capacity_factor=1.0",
                          "scan_chunked=true", "attn_p_dtype=bfloat16"])
    assert ov == {"attn_chunk": 2048, "moe.capacity_factor": 1.0,
                  "scan_chunked": True, "attn_p_dtype": "bfloat16"}


def test_apply_overrides_nested():
    cfg = get_config("grok-1-314b")
    cfg2 = apply_overrides(cfg, {"moe.ep_mode": "shard_map",
                                 "attn_chunk": 512})
    assert cfg2.moe.ep_mode == "shard_map"
    assert cfg2.attn_chunk == 512
    assert cfg.moe.ep_mode == "auto"  # original untouched


def test_optimized_presets_valid():
    for arch, ov in OPTIMIZED_PRESETS.items():
        cfg = apply_overrides(get_config(arch), ov)
        assert cfg.name == arch


def test_adamw_bf16_moments_still_learn():
    from repro.training import adamw

    opt = adamw(0.1, moment_dtype="bfloat16", clip_norm=None)
    p = {"w": jnp.asarray([5.0])}
    st = opt.init(p)
    assert st.mu["w"].dtype == jnp.bfloat16
    for _ in range(60):
        g = {"w": 2 * p["w"]}
        p, st, _ = opt.update(g, st, p)
    # bf16 moment quantization stalls near the optimum; descent from 5.0
    # to <0.6 is the capacity/quality tradeoff being tested
    assert abs(float(p["w"][0])) < 0.6


def test_roofline_recompute_from_artifact():
    # representative artifact (if the sweep has run)
    path = "experiments/dryrun/tinyllama-1.1b_train_4k_single.json"
    if not os.path.exists(path):
        pytest.skip("no dry-run artifacts")
    from benchmarks.roofline_report import recompute

    rec = json.load(open(path))
    row = recompute(rec)
    assert row["dominant"] in ("compute", "memory", "collective")
    assert row["compute_s"] > 0 and row["memory_s"] > 0
    assert 0 < row["useful_ratio"] <= 10.0


def test_build_step_specs_have_shardings():
    from repro.launch.steps import build_step
    from repro.distributed.sharding import AxisRules

    cfg = get_config("tinyllama-1.1b")
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    fn, specs = build_step(cfg, get_shape("decode_32k"), mesh, AxisRules())
    leaves = jax.tree_util.tree_leaves(specs)
    assert all(x.sharding is not None for x in leaves)
    # decode step: token/pos/cache present
    assert specs["batch"]["token"].shape == (128, 1)
    assert specs["cache"]["k"].shape[2] == 32768


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    prev = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_config):
    from repro.launch.compile_cache import CHECKOUT_ROOT, enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == os.path.join(CHECKOUT_ROOT, ".jax_cache")
    assert os.path.isfile(os.path.join(CHECKOUT_ROOT, "pyproject.toml"))
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_compile_cache_env_path_sets_no_other(monkeypatch, tmp_path,
                                              restore_cache_config):
    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "outside"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert enable_compile_cache() == str(tmp_path / "env")
    # the env var is JAX's own; the helper leaves the config path alone
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "outside")


def test_sweep_child_env_is_cpu_pinned(monkeypatch):
    from benchmarks._device_env import subprocess_env

    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8 --foo")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    env = subprocess_env(4)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"] == "--foo --xla_force_host_platform_device_count=4"
