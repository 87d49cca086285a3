"""Launch-layer unit tests: the dry-run's cost instrumentation, the
microbatch divisibility guard (§Perf H4), profiles, and the e2e
training driver at miniature scale.

NOTE: these import repro.launch.dryrun, which sets XLA_FLAGS for 512
host devices — harmless here because jax is already initialised with
1 device by earlier imports in the pytest process; nothing in these
tests builds the production mesh.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _dryrun():
    from repro.launch import dryrun
    return dryrun


def test_collective_bytes_parser():
    hlo = """
  %ag = bf16[128,256]{1,0} all-gather(bf16[8,256]{1,0} %x), dims={0}
  %ar.1 = f32[1024]{0} all-reduce(f32[1024]{0} %y), to_apply=%add
  %tup = (f32[8]{0}, f32[8]{0}) all-to-all(f32[8]{0} %a, f32[8]{0} %b)
  %cp = u32[4]{0} collective-permute(u32[4]{0} %c)
  %not_a_collective = f32[999]{0} add(f32[999]{0} %p, f32[999]{0} %q)
  %tpu = (f32[12,32]{1,0:T(8,128)S(1)}, f32[4]{0:T(128)}) all-reduce(f32[12,32]{1,0:T(8,128)S(1)} %d, f32[4]{0} %e), metadata={op_name="psum"}
"""
    out = _dryrun().collective_bytes(hlo)
    assert out["all-gather"] == 128 * 256 * 2
    # TPU tile layouts nest parentheses inside a tuple result
    assert out["all-reduce"] == 1024 * 4 + (12 * 32 + 4) * 4
    assert out["all-to-all"] == 2 * 8 * 4
    assert out["collective-permute"] == 4 * 4
    assert out["total"] == sum(out[k] for k in
                               ("all-gather", "all-reduce", "reduce-scatter",
                                "all-to-all", "collective-permute"))


_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.utils.compile_cache import use_compile_cache
print(use_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 3).lower(jnp.ones(7)).compile()
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    """$JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache sits at the fixed ``<checkout>/.jax_cache``. Runs in a
    child so this process's jax config is left alone."""
    from repro.utils.compile_cache import CHECKOUT_CACHE_DIR
    root = Path(__file__).resolve().parents[1]
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(root / "src"))
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(compile=from_env)],
        env=env, capture_output=True, text=True, check=True).stdout.split()
    want = str(tmp_path) if from_env else str(root / ".jax_cache")
    assert out == [want, want]
    assert CHECKOUT_CACHE_DIR == root / ".jax_cache"
    if from_env:                  # the compile landed in the env's dir
        assert any(tmp_path.iterdir())


def test_microbatch_divisibility_guard():
    dr = _dryrun()
    from repro.configs import INPUT_SHAPES, get_config
    cfg = get_config("granite-3-2b")
    shape = INPUT_SHAPES["train_4k"]          # B=256
    assert dr.microbatches_for(cfg, shape, n_dp=16) == 16   # 256/16=16 % 16 ok
    assert dr.microbatches_for(cfg, shape, n_dp=32) == 8    # backs off (H4)
    assert dr.microbatches_for(cfg, INPUT_SHAPES["decode_32k"], n_dp=16) == 0


def test_optimized_profile_applies_kept_variants():
    dr = _dryrun()
    from repro.configs import INPUT_SHAPES
    kimi = dr.runtime_config("kimi-k2-1t-a32b", INPUT_SHAPES["train_4k"],
                             optimized=True)
    assert kimi.moe_grouped_dispatch            # H1
    granite = dr.runtime_config("granite-3-2b", INPUT_SHAPES["prefill_32k"],
                                optimized=True)
    assert granite.vocab_round_to == 128        # H2 (49155 % 128 != 0)
    assert granite.attn_chunk_q == 256
    ds = dr.runtime_config("deepseek-7b", INPUT_SHAPES["decode_32k"],
                           optimized=True)
    assert ds.cache_dtype == "float8_e4m3fn"    # H3
    mamba = dr.runtime_config("mamba2-370m", INPUT_SHAPES["decode_32k"],
                              optimized=True)
    assert mamba.cache_dtype == ""              # attention-free: no KV cache
    base = dr.runtime_config("kimi-k2-1t-a32b", INPUT_SHAPES["train_4k"])
    assert not base.moe_grouped_dispatch        # baseline stays faithful


def test_long_500k_runtime_policy():
    dr = _dryrun()
    from repro.configs import INPUT_SHAPES
    dense = dr.runtime_config("command-r-35b", INPUT_SHAPES["long_500k"])
    assert dense.sliding_window == 8192         # documented serving variant
    ssm = dr.runtime_config("mamba2-370m", INPUT_SHAPES["long_500k"])
    assert ssm.sliding_window == 0              # native O(1) state
    assert not dr.shape_applicable("whisper-base", "long_500k")


def test_probe_layer_points():
    dr = _dryrun()
    from repro.configs import get_config
    assert dr._probe_layers(get_config("granite-3-2b")) == (1, 2)
    assert dr._probe_layers(get_config("kimi-k2-1t-a32b")) == (2, 3)   # 1 dense prefix
    assert dr._probe_layers(get_config("llama4-maverick-400b-a17b")) == (2, 4)
    assert dr._probe_layers(get_config("zamba2-1.2b")) == (6, 12)


def test_run_single_descends():
    """Miniature end-to-end run of the training driver."""
    import argparse
    from repro.launch.train import run_single
    ns = argparse.Namespace(preset="tiny", steps=40, batch=8, seq=32,
                            lr=5e-3, seed=0, ckpt="")
    final_ce = run_single(ns)
    assert final_ce < 6.2       # ln(512)=6.24 — beats uniform within 40 steps


def test_fleet_round_trains_on_per_step_microbatches():
    """Regression: the fleet-round local loop re-trained on the
    identical batch every local step. With n_local_steps=2 the round
    must equal two sequential steps on the batch's two *distinct*
    halves. (The fleet round is now built on the shared engine body and
    additionally returns the in-program distribution-stat upload.)"""
    from repro.configs import get_config
    from repro.configs.base import OptimizerConfig
    from repro.core.engine import make_fleet_round
    from repro.models import build_model
    from repro.optim.optimizers import make_optimizer
    from repro.train.steps import make_train_step

    cfg = get_config("granite-3-2b").smoke()
    model = build_model(cfg)
    opt = make_optimizer(OptimizerConfig(name="adam", lr=1e-2))
    round_step = make_fleet_round(model, opt, k=1, n_local_steps=2)

    params = model.init(jax.random.PRNGKey(0))
    B, S = 4, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, B, S), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    sparams = jax.tree.map(lambda x: x[None], params)
    sopt = jax.vmap(opt.init)(sparams)
    out_p, _, stats = jax.jit(round_step)(
        sparams, sopt, batch, jnp.float32(1e-2),
        jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.float32))
    assert stats.shape[0] == 1 and stats.ndim == 2

    step = make_train_step(model, opt)
    p, o = params, opt.init(params)
    for half in (slice(0, 2), slice(2, 4)):
        hb = {k: v[0, half] for k, v in batch.items()}
        p, o, _ = step(p, o, hb, jnp.float32(1e-2))

    # adam's rsqrt amplifies vmap/jit reassociation noise to ~4e-4; the
    # old bug (same batch twice) is two orders of magnitude away (~4e-2)
    got = jax.tree.leaves(jax.tree.map(lambda x: x[0], out_p))
    for g, w in zip(got, jax.tree.leaves(p)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-2, atol=2e-3)

    p2, o2 = params, opt.init(params)
    full = {k: v[0] for k, v in batch.items()}
    for _ in range(2):
        p2, o2, _ = step(p2, o2, full, jnp.float32(1e-2))
    bug_gap = max(float(jnp.abs(g - w).max())
                  for g, w in zip(got, jax.tree.leaves(p2)))
    assert bug_gap > 1e-2, bug_gap


def test_serve_prefill_cache_matches_forward():
    """serve.prefill_into_cache must leave the cache in the same state a
    teacher-forced forward would produce (greedy next tokens agree)."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.launch.serve import prefill_into_cache
    cfg = get_config("granite-3-2b").smoke()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    P = 6
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, P), 0, cfg.vocab_size)
    last_tok, cache = prefill_into_cache(model, params, prompts, model.init_cache(2, P + 2))
    logits, _ = model.forward(params, {"tokens": prompts})
    expect = jnp.argmax(logits[:, -1, :], axis=-1)
    np.testing.assert_array_equal(np.asarray(last_tok), np.asarray(expect))
