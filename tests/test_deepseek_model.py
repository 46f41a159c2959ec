"""DeepSeek-V2-Lite's expert-parallel share (``deepseek_v2_lite_ep4``) on
the CPU at reduced widths: the served path (prefill, then decode through
the cache) against the benchmark's float32 reference of the published
model, YaRN's constants at the published numbers, and the dense models'
trees and programs left as they were by the leading-layer and expert-share
support."""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as cfgs
import repro.kernels as kernels
from repro.models import build
from repro.models.layers import (yarn_correction_range, yarn_frequencies,
                                 yarn_mscale)
from repro.models.mla import score_factor
from repro.runtime.serve_loop import ServeConfig, make_generate_program
from repro.tune import cache as tune_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench.lib import spec, weights  # noqa: E402

FAMILY = spec.family("deepseek_v2")
FULL = cfgs.get("deepseek_v2_lite_ep4")


def test_the_published_yarn_constants():
    y = FULL.rope_yarn
    assert yarn_mscale(y.factor, y.mscale_all_dim) == pytest.approx(
        1.26081, abs=1e-5)  # 0.1 * 0.707 * ln 40 + 1 = 1.2608038
    assert yarn_correction_range(y, FULL.qk_rope_head_dim,
                                 FULL.rope_theta) == (10, 23)
    assert 192 ** -0.5 * score_factor(FULL) == pytest.approx(0.11472,
                                                             abs=5e-6)
    # the program's frequencies are the reference's
    m = spec.cell("dsv2lite-gen-batch").model
    assert FAMILY.yarn_correction_range(m) == (10, 23)
    np.testing.assert_allclose(
        yarn_frequencies(FULL.qk_rope_head_dim, FULL.rope_theta, y),
        FAMILY.yarn_inv_freq(m), rtol=1e-6)
    assert FAMILY.softmax_scale(m) == pytest.approx(
        192 ** -0.5 * score_factor(FULL), rel=1e-12)


@pytest.mark.parametrize("seed", [2**31 + 5, 11])
def test_prefill_and_decode_match_the_reference(seed):
    """Reduced widths, float32, YaRN, a leading dense layer, two MoE
    layers over 2 of the router's 8 experts, the untied head: each
    decoded position's logits are the reference's full forward pass."""
    cfg = cfgs.reduced(FULL)
    assert cfg.first_dense_layers == 1 and cfg.moe.n_held < cfg.moe.n_experts
    m = FAMILY.reduced(cfg)
    api = build(cfg)
    weights.check_layout(jax.eval_shape(api.init, jax.random.PRNGKey(0)),
                         FAMILY, m)
    params = jax.jit(FAMILY.program_params, static_argnums=1)(
        weights.seed_words(seed), m)
    B, P, N = 2, 12, 5
    toks = np.random.default_rng(seed).integers(0, m.vocab_size, (B, P + N),
                                                dtype=np.int32)
    logits, caches = jax.jit(lambda p, b: api.prefill(p, b, seq_budget=P + N))(
        params, {"tokens": jnp.asarray(toks[:, :P])})
    got = [logits]
    decode = jax.jit(api.decode)
    for j in range(N - 1):
        logits, caches = decode(params, {
            "tokens": jnp.asarray(toks[:, P + j:P + j + 1]),
            "cache_index": jnp.asarray(P + j, jnp.int32)}, caches)
        got.append(logits)
    ref = FAMILY.logits(seed, m, toks[:, :P + N - 1],
                        np.arange(P - 1, P + N - 1))
    np.testing.assert_allclose(np.stack(got, 1), ref, rtol=1e-4, atol=1e-4)


def test_the_full_configuration_is_the_published_one():
    c = FULL
    assert (c.n_layers, c.first_dense_layers, c.d_model, c.n_heads) == (
        27, 1, 2048, 16)
    assert (c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim,
            c.kv_lora_rank, c.q_lora_rank) == (128, 64, 128, 512, 0)
    assert (c.d_ff, c.moe.d_ff_expert, c.moe.dense_residual_ff) == (
        10944, 1408, 2816)
    assert (c.moe.n_experts, c.moe.top_k, c.moe.n_held) == (64, 6, 16)
    assert not c.moe.renormalize and c.moe.dropless
    assert c.vocab_size == 102400 and not c.tie_embeddings
    total, _ = c.param_counts()
    assert 4.85e9 < total < 4.95e9  # 9.8 GB in bf16


def _digest(tree) -> str:
    s = ";".join(f"{jax.tree_util.keystr(k)}:{a.shape}:{a.dtype}"
                 for k, a in jax.tree_util.tree_leaves_with_path(tree))
    return hashlib.sha256(s.encode()).hexdigest()


def test_dense_trees_are_unchanged_by_the_leading_layer():
    """qwen3-1.7b's parameter and cache trees (leaf paths, shapes and
    dtypes) as they were before a model could have leading dense layers."""
    api = build(cfgs.get("qwen3_1p7b"))
    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: api.make_caches(64, 640))
    assert "lead" not in params["blocks"] and "lead" not in caches
    assert _digest(params) == (
        "26e65fd6aeaaa8d51bf877d430745aa8e21f1a4b60d8f4da81fc020ea67409d6")
    assert _digest(caches) == (
        "1f3948b1b2b023f7ca9c7a036f25225322d6998a3e017d774f671f2869dd7348")


# sha256 of the StableHLO text that the dense models' programs lowered to
# for the TPU before a model could have leading dense layers or a share of
# its experts: the generate programs at the benchmark's shapes (batch,
# prompt, new tokens) and a training step's gradient (new tokens 0)
DENSE_PROGRAMS = {
    ("qwen3_1p7b", 64, 512, 128):
        "1070d889f8afb64dfa2f6eaf7c11bb3fce5822922f47788de955fda03869a56d",
    ("qwen3_1p7b", 1, 128, 32):
        "ac525d692e5092f33d7920e237fe6a5d54e2c3d5f0c57e5b6db5a139d2cd2ce2",
    ("minicpm_2b", 2, 2048, 128):
        "a9ad113c6dd645ce3ede50f3135c1a483fb459d05eb2f0b00143074d3b249a4a",
    ("qwen3_1p7b", 2, 256, 0):
        "242e8ecbfb672c27af986979fe05c8ad52a821e9f79d055ffea3250ac0a68a36",
    ("minicpm_2b", 2, 256, 0):
        "2fbfdb0fd713b91827787ff2e1d0d64e3a94dda5ec91d2e65a276bdef32c3522",
}


def _lowered_for_tpu(arch, B, P, N) -> str:
    api = build(cfgs.get(arch))
    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((B, P), jnp.int32)
    if N:
        sc = ServeConfig(max_new_tokens=N, prompt_len=P, batch_per_task=B)
        fn = make_generate_program(api, sc, params).fn
        args = (params, {"tokens": tokens})
    else:
        fn = jax.grad(lambda p, b: api.train_loss(p, b)[0])
        args = (params, {"tokens": tokens, "targets": tokens})
    traced = jax.jit(fn).trace(*args)
    return traced.lower(lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("case", sorted(DENSE_PROGRAMS),
                         ids=lambda c: "-".join(map(str, c)))
def test_dense_programs_lower_as_before(case):
    prev = tune_cache.set_cache(None)  # the kernels' default tilings
    try:
        with kernels.backend("xla"):
            text = _lowered_for_tpu(*case)
    finally:
        tune_cache.set_cache(prev)
    assert hashlib.sha256(text.encode()).hexdigest() == DENSE_PROGRAMS[case]
