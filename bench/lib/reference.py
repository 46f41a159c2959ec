"""The plain reference: a dense decoder's forward pass in float32.

It follows the published description of the architecture (Qwen3 and
MiniCPM are both Llama-style decoders: RMSNorm before each block, RoPE
on rotated halves, grouped-query attention, a SwiGLU MLP, tied
embeddings), with the numbers of the configuration file as it is run.
It imports nothing of the program and takes nothing that the program
made: its weights are drawn again from the seed (:mod:`.weights`), one
layer at a time, so that it fits beside nothing else on the chip.

``quantize`` gives the control: the same pass with every weight matrix
rounded to float8 (e4m3, one scale per output channel), the step below
the bfloat16 that the configurations state.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W
from .spec import Model

F32 = jnp.float32
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def fp8_round(w, axis):
    """``w`` through float8 e4m3 with one scale per slice along ``axis``
    (the reduction axis of its matmul)."""
    w = w.astype(F32)
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (R, T, heads, hd), rotated halves, positions 0..T-1."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnums=(2, 3))
def _block(x, w, m: Model, quantize: bool):
    w = {k: v.astype(F32) for k, v in w.items()}
    if quantize:
        w = {k: fp8_round(v, 0) if v.ndim == 2 else v for k, v in w.items()}
    R, T, _ = x.shape
    H, K, hd = m.num_attention_heads, m.num_key_value_heads, m.head_dim
    eps = m.rms_norm_eps
    h = _rms(x, w["mixer_norm/scale"], eps)
    q = (h @ w["attn/wq"]).reshape(R, T, H, hd)
    k = (h @ w["attn/wk"]).reshape(R, T, K, hd)
    v = (h @ w["attn/wv"]).reshape(R, T, K, hd)
    if m.qk_norm:
        q = _rms(q, w["attn/q_norm"], eps)
        k = _rms(k, w["attn/k_norm"], eps)
    q, k = _rope(q, m.rope_theta), _rope(k, m.rope_theta)
    k = jnp.repeat(k, H // K, axis=2)  # query head i reads kv head i // (H/K)
    v = jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("rqhd,rkhd->rhqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("rhqk,rkhd->rqhd", p, v).reshape(R, T, H * hd)
    x = x + (o @ w["attn/wo"]) * m.residual_scale
    h = _rms(x, w["mlp_norm/scale"], eps)
    mlp = (jax.nn.silu(h @ w["mlp/wg"]) * (h @ w["mlp/wi"])) @ w["mlp/wo"]
    return x + mlp * m.residual_scale


@partial(jax.jit, static_argnums=(2,))
def _embed(words, tokens, m: Model):
    return W.embedding(words, m)[tokens].astype(F32) * m.scale_emb


@partial(jax.jit, static_argnums=(3, 4))
def _head(words, x, rows_pos, m: Model, quantize: bool):
    table = W.embedding(words, m).astype(F32)
    if quantize:
        table = fp8_round(table, 1)
    x = _rms(x, W.final_norm(words, m).astype(F32), m.rms_norm_eps)
    x = jnp.take(x, rows_pos, axis=1) * m.logit_scale
    return jnp.einsum("rnd,vd->rnv", x, table)


def logits(seed: int, m: Model, tokens, out_positions, *,
           quantize: bool = False):
    """Logits (rows, len(out_positions), vocab), float32, of ``tokens``
    (rows, T) at the positions ``out_positions``."""
    words = W.seed_words(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    lay = jax.jit(W.layer, static_argnums=1)
    with jax.default_matmul_precision("highest"):
        x = _embed(words, tokens, m)
        for i in range(m.num_hidden_layers):
            x = _block(x, lay(words, m, i), m, quantize)
        return _head(words, x, jnp.asarray(out_positions, jnp.int32), m,
                     quantize)


def served_gap(seed: int, m: Model, prompts, served, *,
               quantize: bool = False, chunk: int = 0) -> dict:
    """How far the served tokens' logits lie below the reference's best.

    ``prompts`` (R, P) and ``served`` (R, N) are what the program was
    given and returned.  The reference runs once over prompt + served
    tokens; the logit at position P-1+j judges served token j.  Returns
    ``max_gap`` (the widest gap, the number compared) and ``control_gap``,
    the widest gap of the tokens that the float8 pass would put first at
    the same positions (when ``quantize``).  ``chunk`` rows at a time
    (0: all) bounds the reference's memory."""
    prompts, served = np.asarray(prompts), np.asarray(served)
    R, P = prompts.shape
    N = served.shape[1]
    chunk = chunk or R
    gaps, cgaps = [], []
    for r0 in range(0, R, chunk):
        p, s = prompts[r0:r0 + chunk], served[r0:r0 + chunk]
        toks = np.concatenate([p, s[:, :N - 1]], axis=1)
        pos = np.arange(P - 1, P - 1 + N)
        ref = logits(seed, m, toks, pos)
        best = ref.max(-1)
        got = jnp.take_along_axis(ref, jnp.asarray(s)[..., None], -1)[..., 0]
        gaps.append(np.asarray(best - got))
        if quantize:
            ctl = logits(seed, m, toks, pos, quantize=True)
            pick = jnp.argmax(ctl, -1)
            cg = best - jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
            cgaps.append(np.asarray(cg))
    out = {"max_gap": float(np.max(np.concatenate(gaps))),
           "tokens": int(R * N)}
    if quantize:
        out["control_gap"] = float(np.max(np.concatenate(cgaps)))
    return out
