"""Prefill+decode must agree with recomputing prefill at every step
(KV-cache correctness across architectures, incl. MLA and SSM states)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as cfgs
from repro.models import build

# one representative per cache type: GQA, MLA, pure-SSM, hybrid, enc-dec
ARCHS = ["llama3p2_1b", "minicpm3_4b", "falcon_mamba_7b",
         "jamba_1p5_large_398b", "whisper_tiny"]


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_incremental_prefill(arch):
    cfg = cfgs.reduced(cfgs.get(arch))
    if cfg.moe is not None:
        # capacity-based (dropping) MoE routes per group: a 1-token decode
        # group never drops, a prefill group might — that's an inherent
        # train/serve inconsistency of dropping MoEs, not a cache bug.
        # Test with capacity high enough that nothing drops.
        import dataclasses

        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  capacity_factor=8.0))
    api = build(cfg)
    key = jax.random.PRNGKey(0)
    params = api.init(key)
    B, T = 2, 12
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (B, T + 4), 0,
                                cfg.vocab_size)
    extras = {}
    if cfg.is_encoder_decoder:
        extras["enc_frames"] = jax.random.normal(
            key, (B, cfg.encoder_seq_len, cfg.d_model), cfg.dtype)

    prefill = jax.jit(lambda p, b: api.prefill(p, b, seq_budget=T + 8))
    # reference: prefill on progressively longer prefixes
    ref_logits = []
    for t in range(T, T + 4):
        lg, _ = prefill(params, {"tokens": tokens[:, :t + 1], **extras})
        ref_logits.append(np.asarray(lg, np.float32))

    # decode path: prefill T tokens then feed one token at a time
    logits, caches = prefill(params, {"tokens": tokens[:, :T], **extras})
    decode = jax.jit(api.decode)
    got = []
    for i in range(4):
        dbatch = {"tokens": tokens[:, T + i:T + i + 1],
                  "cache_index": jnp.asarray(T + i, jnp.int32)}
        logits, caches = decode(params, dbatch, caches)
        got.append(np.asarray(logits, np.float32))

    for i, (a, b) in enumerate(zip(got, ref_logits)):
        np.testing.assert_allclose(a, b, atol=2e-3, rtol=2e-3,
                                   err_msg=f"{arch} step {i}")


def _generate_case(arch):
    cfg = cfgs.reduced(cfgs.get(arch))
    if cfg.moe is not None:  # nothing drops (see above)
        import dataclasses

        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  capacity_factor=8.0))
    api = build(cfg)
    key = jax.random.PRNGKey(2)
    params = api.init(key)
    B, P, N = 2, 12, 6
    prompts = jax.random.randint(jax.random.fold_in(key, 1), (B, P), 0,
                                 cfg.vocab_size)
    return api, params, prompts, N


def _assert_greedy(api, params, prompts, generated):
    """Each generated token is the greedy choice of a prefill over the
    prompt and the tokens generated before it (to a rounding of its
    logit)."""
    P, N = prompts.shape[1], generated.shape[1]
    prefill = jax.jit(lambda p, t: api.prefill(p, {"tokens": t})[0])
    seq = np.concatenate([np.asarray(prompts), generated], axis=1)
    for j in range(N):
        ref = np.asarray(prefill(params, seq[:, :P + j]), np.float32)
        chosen = np.take_along_axis(ref, generated[:, j:j + 1], 1)[:, 0]
        np.testing.assert_allclose(chosen, ref.max(-1), atol=2e-4,
                                   err_msg=f"token {j}")


@pytest.mark.parametrize("arch", ARCHS[:4])
def test_generate_program_matches_greedy_prefill(arch):
    """The served program (prefill, then the scanned decode that writes
    each token into the carried cache stack) against prefill alone."""
    from repro.runtime.serve_loop import ServeConfig, make_generate_program

    api, params, prompts, N = _generate_case(arch)
    sc = ServeConfig(max_new_tokens=N, prompt_len=prompts.shape[1],
                     batch_per_task=prompts.shape[0])
    program = make_generate_program(api, sc, params)
    out = jax.jit(program.fn)(params, {"tokens": prompts})
    _assert_greedy(api, params, prompts, np.asarray(out["generated"]))


def test_generate_program_batched_through_service():
    """The same through ``Service.execute_batch``: one task padded to a
    batch of two and vmapped, as the chat cell serves it."""
    from repro.core import Service
    from repro.runtime.serve_loop import ServeConfig, make_generate_program

    api, params, prompts, N = _generate_case("llama3p2_1b")
    sc = ServeConfig(max_new_tokens=N, prompt_len=prompts.shape[1],
                     batch_per_task=prompts.shape[0])
    program = make_generate_program(api, sc, params)
    svc = Service(None)
    [out] = svc.execute_batch(program, [{"tokens": np.asarray(prompts)}],
                              pad_to=2)
    _assert_greedy(api, params, prompts, np.asarray(out["generated"]))
