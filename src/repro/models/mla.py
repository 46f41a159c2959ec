"""Multi-head Latent Attention (DeepSeek-V2 / MiniCPM3 style).

Train/prefill materialize per-head K/V from the latent; decode uses the
*absorbed* formulation — scores and values are computed directly against the
compressed latent cache (kv_lora_rank + rope dims per token), which is the
whole point of MLA for serving: the 32k-decode cache shrinks by ~an order of
magnitude vs GQA.

With ``cfg.rope_yarn`` the rope part takes YaRN's frequencies and the
scores DeepSeek-V2's softmax scale, ``q_head_dim ** -0.5 * mscale ** 2``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import ModelConfig
from .attention import write_row
from .layers import apply_rope, dense_init, yarn_frequencies, yarn_mscale

NEG_INF = -2.0e38


def init_mla(key, cfg: ModelConfig):
    d = cfg.d_model
    nope, rope, vdim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    H = cfg.n_heads
    ks = jax.random.split(key, 6)
    p = {
        "wkv_a": dense_init(ks[2], (d, cfg.kv_lora_rank + rope), dtype=cfg.pdtype),
        "kv_a_norm": jnp.ones((cfg.kv_lora_rank,), cfg.pdtype),
        "wkv_b": dense_init(
            ks[3], (cfg.kv_lora_rank, H * (nope + vdim)),
            in_axis_size=cfg.kv_lora_rank, dtype=cfg.pdtype,
        ),
        "wo": dense_init(ks[4], (H * vdim, d), dtype=cfg.pdtype),
    }
    if cfg.q_lora_rank:
        p["wq_a"] = dense_init(ks[0], (d, cfg.q_lora_rank), dtype=cfg.pdtype)
        p["q_a_norm"] = jnp.ones((cfg.q_lora_rank,), cfg.pdtype)
        p["wq_b"] = dense_init(
            ks[1], (cfg.q_lora_rank, H * (nope + rope)), dtype=cfg.pdtype
        )
    else:
        p["wq"] = dense_init(ks[0], (d, H * (nope + rope)), dtype=cfg.pdtype)
    return p


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, cfg: ModelConfig):
    y = cfg.rope_yarn
    if y is None:
        return apply_rope(x, positions, cfg.rope_theta)
    out = apply_rope(x, positions, cfg.rope_theta,
                     yarn_frequencies(x.shape[-1], cfg.rope_theta, y))
    amplitude = yarn_mscale(y.factor, y.mscale) / yarn_mscale(
        y.factor, y.mscale_all_dim)
    if amplitude == 1.0:
        return out
    return (out.astype(jnp.float32) * amplitude).astype(out.dtype)


def score_factor(cfg: ModelConfig) -> float:
    """The softmax scale over ``(nope + rope) ** -0.5``: YaRN's mscale
    squared where ``mscale_all_dim`` is set, else 1."""
    y = cfg.rope_yarn
    if y is None or not y.mscale_all_dim:
        return 1.0
    return yarn_mscale(y.factor, y.mscale_all_dim) ** 2


def _queries(p, x, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    H = cfg.n_heads
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dt = cfg.dtype
    if cfg.q_lora_rank:
        ql = _rms(x @ p["wq_a"].astype(dt), p["q_a_norm"])
        q = (ql @ p["wq_b"].astype(dt)).reshape(B, S, H, nope + rope)
    else:
        q = (x @ p["wq"].astype(dt)).reshape(B, S, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = _rope(q_rope, positions, cfg)
    return q_nope, q_rope


def _latent(p, x, cfg: ModelConfig, positions):
    """Returns (c_kv (B,S,R) normalized latent, k_rope (B,S,1,rope))."""
    dt = cfg.dtype
    kv_a = x @ p["wkv_a"].astype(dt)
    c_kv = _rms(kv_a[..., : cfg.kv_lora_rank], p["kv_a_norm"])
    k_rope = kv_a[..., cfg.kv_lora_rank:][..., None, :]  # single rope head
    k_rope = _rope(k_rope, positions, cfg)
    return c_kv, k_rope[..., 0, :]


def apply_mla_train(p, x, cfg: ModelConfig):
    """Materialized path (train/prefill). Returns (B,S,d)."""
    B, S, _ = x.shape
    H = cfg.n_heads
    nope, rope, vdim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    positions = jnp.arange(S)
    q_nope, q_rope = _queries(p, x, cfg, positions)
    c_kv, k_rope = _latent(p, x, cfg, positions)
    kv = (c_kv @ p["wkv_b"].astype(cfg.dtype)).reshape(B, S, H, nope + vdim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    # assemble effective q/k with rope part appended; K==H (no GQA in MLA)
    q = jnp.concatenate([q_nope, q_rope], -1)
    if score_factor(cfg) != 1.0:  # the kernel scales by (nope+rope)**-0.5
        q = (q.astype(jnp.float32) * score_factor(cfg)).astype(q.dtype)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                                  (B, S, H, rope))], -1)
    from repro.kernels import flash_attention_dispatch

    out = flash_attention_dispatch(q, k, v, causal=True)
    out = out.reshape(B, S, H * vdim)
    return out @ p["wo"].astype(cfg.dtype)


def apply_mla_prefill(p, x, cfg: ModelConfig):
    out = apply_mla_train(p, x, cfg)
    positions = jnp.arange(x.shape[1])
    c_kv, k_rope = _latent(p, x, cfg, positions)
    return out, {"c_kv": c_kv, "k_rope": k_rope}


def apply_mla_decode(p, x, cache, cfg: ModelConfig, *, cache_index, layer=None):
    """Absorbed decode. cache: {"c_kv": (B,S,R), "k_rope": (B,S,rope)}, or
    with ``layer`` the stacked (L,B,S,*); the new token's latent is written
    at ``cache_index`` (of ``layer``). Returns (out, new_cache)."""
    B = x.shape[0]
    H = cfg.n_heads
    nope, rope, vdim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    R = cfg.kv_lora_rank
    positions = jnp.full((1,), cache_index, dtype=jnp.int32)
    q_nope, q_rope = _queries(p, x, cfg, positions)  # (B,1,H,*)
    c_new, kr_new = _latent(p, x, cfg, positions)
    c_all, c_kv = write_row(cache["c_kv"], c_new, cache_index, layer)
    kr_all, k_rope = write_row(cache["k_rope"], kr_new, cache_index, layer)

    wkv_b = p["wkv_b"].astype(cfg.dtype).reshape(R, H, nope + vdim)
    w_k, w_v = wkv_b[..., :nope], wkv_b[..., nope:]
    # absorb: q_abs (B,H,R)
    q_abs = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_k,
                       preferred_element_type=jnp.float32)
    s = jnp.einsum("bhr,bsr->bhs", q_abs, c_kv.astype(jnp.float32))
    s = s + jnp.einsum("bhp,bsp->bhs", q_rope[:, 0].astype(jnp.float32),
                       k_rope.astype(jnp.float32))
    s = s * ((nope + rope) ** -0.5 * score_factor(cfg))
    S = c_kv.shape[1]
    mask = jnp.arange(S) <= cache_index
    s = jnp.where(mask[None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", w, c_kv.astype(jnp.float32))
    out = jnp.einsum("bhr,rhv->bhv", o_lat, w_v.astype(jnp.float32))
    out = out.reshape(B, 1, H * vdim).astype(cfg.dtype)
    return out @ p["wo"].astype(cfg.dtype), {"c_kv": c_all, "k_rope": kr_all}


def make_empty_mla_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=None):
    dtype = dtype or cfg.dtype
    return {
        "c_kv": jnp.zeros((batch, seq_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, seq_len, cfg.qk_rope_head_dim), dtype),
    }
