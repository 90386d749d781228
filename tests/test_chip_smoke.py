"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and its
kernel and serving phases pass at reduced widths on the CPU (Pallas
kernels in interpret mode), with bfloat16 weights like the chip run."""

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from repro.configs import get_config
from repro.models.transformer import RuntimeOpts, init_params_jit
from repro.serving.http import build_model

OPTS = RuntimeOpts(q_chunk=16, kv_chunk=16, remat=False, quantized_kv=True,
                   moe_capacity_factor=0.0)


def test_refuses_to_run_without_a_tpu():
    with pytest.raises(SystemExit, match="no TPU found"):
        chip_smoke.check_device(1)


def test_kernel_phase_at_reduced_widths():
    errs = chip_smoke.kernel_phase(kh=2, hd=32, page=64)
    assert set(errs) == {"paged_decode", "paged_prefill", "varlen"}
    assert max(errs.values()) < 1e-4  # float32 oracles, same arithmetic


@pytest.fixture(scope="module")
def bf16_tiny():
    cfg = get_config("llama2-7b-tiny")
    params = init_params_jit(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    prompts = chip_smoke.make_prompts(cfg.vocab_size, seed=0, n=5,
                                      lens=(3, 40))
    ref = chip_smoke.reference_logprobs(cfg, params, prompts)
    return cfg, params, prompts, ref


@pytest.mark.parametrize("tick_mode", ["chunked", "packed"])
def test_serve_phase_bf16_over_http(bf16_tiny, tick_mode):
    """Five requests through the HTTP service with two slots and 16-token
    prefill chunks: every one finishes, the pool drains, and first-token
    logprobs match forward_train."""
    cfg, params, prompts, ref = bf16_tiny
    assert jax.tree_util.tree_leaves(params)[0].dtype == jnp.bfloat16
    out = chip_smoke.serve_phase(cfg, params, OPTS, prompts, ref,
                                 tick_mode=tick_mode, num_pages=24,
                                 page_size=4, max_slots=2, max_tokens=4,
                                 prefill_chunk=16, timeout_s=120.0)
    assert len(out["streams"]) == len(prompts)
    assert out["worst_gap"] <= chip_smoke.LOGPROB_TOL


def test_build_model_serves_the_config_it_is_given():
    cfg, params, _ = build_model("llama2-7b-tiny", seed=1)
    assert cfg.name == "llama2-7b-tiny" and cfg.vocab_size == 256
    assert jax.tree_util.tree_leaves(params)[0].dtype == jnp.float32
    full = jax.eval_shape(lambda: build_model("llama2-7b")[1])
    leaves = jax.tree_util.tree_leaves(full)
    assert {a.dtype for a in leaves} == {jnp.dtype(jnp.bfloat16)}
    assert full["embed"].shape == (32000, 4096)


def test_compile_cache_dir_is_the_env_var_or_a_fixed_repo_path(monkeypatch):
    from repro.launch import compile_cache as cc

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cc.ENV_VAR, "/elsewhere/cache")
    assert cc.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the var
    monkeypatch.delenv(cc.ENV_VAR)
    try:
        assert cc.enable_compile_cache() == str(cc.REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(cc.REPO_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert cc.REPO_CACHE_DIR.parent.joinpath("chip_smoke.py").is_file()
