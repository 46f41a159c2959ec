"""Shared neural-net layers: norms, RoPE, MLPs, embeddings.

Everything is a pure function over explicit parameter pytrees (no framework).
``init_*`` functions return param dicts; ``apply`` counterparts consume them.
Initializers take an explicit PRNG key so stacked (scanned) layers can be
initialized with jax.vmap over split keys.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import ModelConfig


# --------------------------------------------------------------------- #
# initializers
# --------------------------------------------------------------------- #
def dense_init(key, shape, in_axis_size: int | None = None, dtype=jnp.float32):
    """Truncated-normal fan-in initializer (LeCun-normal-ish)."""
    fan_in = in_axis_size if in_axis_size is not None else shape[0]
    std = fan_in**-0.5
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# --------------------------------------------------------------------- #
# norms
# --------------------------------------------------------------------- #
def init_norm(cfg: ModelConfig, dim: int | None = None):
    d = dim or cfg.d_model
    p = {"scale": jnp.ones((d,), cfg.pdtype)}
    if cfg.norm_type == "layernorm":
        p["bias"] = jnp.zeros((d,), cfg.pdtype)
    return p


def apply_norm(p, x, cfg: ModelConfig, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_headwise(scale, x, eps: float = 1e-6):
    """qk-norm: RMSNorm over the head_dim of (..., heads, head_dim)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------- #
# RoPE
# --------------------------------------------------------------------- #
def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek-V2's ``yarn_get_mscale``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(yarn, head_dim: int, theta: float) -> tuple[int, int]:
    """The rope pairs [low, high) over which YaRN ramps from the original
    frequencies to those divided by ``factor`` (DeepSeek-V2's
    ``yarn_find_correction_range``)."""
    def pair(rotations):
        return (head_dim * math.log(yarn.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    return (max(math.floor(pair(yarn.beta_fast)), 0),
            min(math.ceil(pair(yarn.beta_slow)), head_dim - 1))


def yarn_frequencies(head_dim: int, theta: float, yarn) -> jnp.ndarray:
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` inverse frequencies:
    pairs below the correction range keep theirs, pairs above it take
    theirs over ``factor``, with a linear ramp between."""
    extra = rope_frequencies(head_dim, theta)
    low, high = yarn_correction_range(yarn, head_dim, theta)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / yarn.factor * ramp + extra * (1.0 - ramp)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               freqs: jnp.ndarray | None = None) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq).
    ``freqs``: the inverse frequencies (hd/2,), by default plain RoPE's."""
    if freqs is None:
        freqs = rope_frequencies(x.shape[-1], theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    sin = jnp.sin(angles)[..., None, :]  # (..., seq, 1, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- #
# MLPs
# --------------------------------------------------------------------- #
def init_dense_mlp(key, cfg: ModelConfig, d_ff: int | None = None):
    ff = d_ff or cfg.d_ff
    d = cfg.d_model
    if cfg.mlp_act == "swiglu":
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "wi": dense_init(k1, (d, ff), dtype=cfg.pdtype),
            "wg": dense_init(k2, (d, ff), dtype=cfg.pdtype),
            "wo": dense_init(k3, (ff, d), dtype=cfg.pdtype),
        }
    k1, k2 = jax.random.split(key)
    return {
        "wi": dense_init(k1, (d, ff), dtype=cfg.pdtype),
        "wo": dense_init(k2, (ff, d), dtype=cfg.pdtype),
    }


def apply_dense_mlp(p, x, cfg: ModelConfig):
    dt = cfg.dtype
    if cfg.mlp_act == "swiglu":
        h = jax.nn.silu(x @ p["wg"].astype(dt)) * (x @ p["wi"].astype(dt))
    else:
        h = jax.nn.gelu(x @ p["wi"].astype(dt))
    return h @ p["wo"].astype(dt)


# --------------------------------------------------------------------- #
# embeddings / unembedding
# --------------------------------------------------------------------- #
def init_embed(key, cfg: ModelConfig):
    return {"table": embed_init(key, (cfg.vocab_size, cfg.d_model), cfg.pdtype)}


def apply_embed(p, tokens, cfg: ModelConfig):
    return jnp.take(p["table"].astype(cfg.dtype), tokens, axis=0)


def apply_unembed(p, x, cfg: ModelConfig):
    """Returns fp32 logits."""
    return (x.astype(jnp.float32)) @ (p["table"].astype(jnp.float32).T)


def cross_entropy_loss(logits: jnp.ndarray, targets: jnp.ndarray, mask=None):
    """Mean token cross-entropy in fp32. logits (B,S,V), targets (B,S)."""
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(nll.dtype)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
