"""Launch layer: step builders, input specs, HLO counting, mesh helpers."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.launch.analysis import Roofline, model_flops_for
from repro.launch.hlo_count import analyze_hlo
from repro.launch.mesh import data_axis_size, make_host_mesh, mesh_chip_count
from repro.launch.steps import build_step, input_specs


def test_host_mesh():
    mesh = make_host_mesh()
    assert set(mesh.axis_names) == {"data", "model"}
    assert mesh_chip_count(mesh) >= 1
    assert data_axis_size(mesh) >= 1


@pytest.mark.parametrize("shape_name", sorted(SHAPES))
def test_build_step_shapes(shape_name):
    """Abstract args carry the assigned shapes; shardings mirror args."""
    mesh = make_host_mesh()
    built = build_step("llama3.2-1b", shape_name, mesh)
    spec = SHAPES[shape_name]
    flat_args = jax.tree_util.tree_leaves(built.abstract_args)
    flat_shard = jax.tree_util.tree_leaves(built.in_shardings)
    assert len(flat_args) == len(flat_shard)
    if spec.kind == "train":
        params, opt, batch = built.abstract_args
        assert batch["tokens"].shape == (spec.global_batch, spec.seq_len)
    elif spec.kind == "prefill":
        tokens = built.abstract_args[1]
        assert tokens.shape == (spec.global_batch, spec.seq_len)
    else:  # decode
        tokens = built.abstract_args[1]
        assert tokens.shape == (spec.global_batch, 1)
        cache = built.abstract_args[2]
        assert cache["kv"]["k"].shape[3] == spec.seq_len  # cache slots
        assert built.donate == (2,)


def test_input_specs_no_allocation():
    """input_specs returns ShapeDtypeStructs only (no device buffers)."""
    mesh = make_host_mesh()
    args = input_specs("qwen2.5-3b", "decode_32k", mesh)
    for leaf in jax.tree_util.tree_leaves(args):
        assert isinstance(leaf, jax.ShapeDtypeStruct)


def test_swa_cache_is_ring_sized():
    mesh = make_host_mesh()
    built = build_step("h2o-danube3-4b", "decode_32k", mesh)
    cache = built.abstract_args[2]
    cfg = get_config("h2o-danube3-4b")
    assert cache["kv"]["k"].shape[3] == cfg.sliding_window


def test_ssm_decode_has_o1_state():
    """long_500k for mamba carries O(1) state, not a 500k KV cache."""
    mesh = make_host_mesh()
    built = build_step("falcon-mamba-7b", "long_500k", mesh)
    cache = built.abstract_args[2]
    assert "kv" not in cache
    assert cache["ssm_state"]["ssm"].shape[-1] == 16   # d_state, not seq


def test_model_flops_scaling():
    cfg = get_config("llama3.2-1b")
    f_train = model_flops_for(cfg, SHAPES["train_4k"])
    f_dec = model_flops_for(cfg, SHAPES["decode_32k"])
    # 6·N·(B·S) vs 2·N·B
    ratio = f_train / f_dec
    assert ratio == pytest.approx(3 * 4096 * 256 / 128, rel=0.01)


def test_moe_active_params_flops():
    cfg = get_config("qwen3-moe-30b")
    f = model_flops_for(cfg, SHAPES["decode_32k"])
    # active ~3.3B of 30.5B total: 2 * N_active * 128
    n_active = f / (2 * 128)
    assert 2e9 < n_active < 6e9


def test_hlo_count_loop_scaling():
    def body(x, w):
        return x @ w, None

    W = jax.ShapeDtypeStruct((4, 64, 64), jnp.float32)
    X = jax.ShapeDtypeStruct((8, 64), jnp.float32)
    c = jax.jit(
        lambda x, ws: jax.lax.scan(body, x, ws)[0]
    ).lower(X, W).compile()
    k = analyze_hlo(c.as_text())
    assert k.flops == 4 * 2 * 8 * 64 * 64     # trip count × dot flops


def test_roofline_terms():
    r = Roofline(
        arch="a", shape="s", mesh_desc="m", chips=256,
        hlo_flops=197e12, hlo_bytes=819e9, collective_link_bytes=50e9,
        model_flops=197e12 * 256,
    )
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(1.0)
    assert r.collective_s == pytest.approx(1.0)
    assert r.step_time_s == pytest.approx(1.0)
    assert r.useful_flops_fraction == pytest.approx(1.0)
    assert r.mfu == pytest.approx(1.0)
    assert r.bottleneck in ("compute", "memory", "collective")


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_directory(monkeypatch, tmp_path, env_dir):
    """Unset: the cache is <repo>/.jax_cache.  Set: JAX reads the variable
    itself and the helper sets no directory of its own."""
    import os

    from repro.compile_cache import REPO_CACHE_DIR, use_compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert str(REPO_CACHE_DIR) == os.path.join(repo, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            assert use_compile_cache() == str(REPO_CACHE_DIR)
            assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
        else:
            want = str(tmp_path / env_dir)
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
            assert use_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
