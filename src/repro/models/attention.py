"""GQA attention: training (chunked flash-style), prefill, and cached decode.

The XLA path implements attention as a *nested-scan online-softmax* (flash
attention in pure jnp): an outer scan over query chunks and an inner scan over
KV chunks with a running (max, denominator, accumulator) carry.  This keeps
peak memory O(chunk^2) instead of O(seq^2) so 32k-token prefill lowers with a
sane memory footprint.  The Pallas kernel in ``repro.kernels.flash_attention``
is a drop-in replacement on TPU (enabled via ``repro.kernels.set_backend``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import custom_batching
from jax.experimental.layout import Layout, with_layout_constraint

from .common import ModelConfig
from .layers import apply_rope, dense_init, rms_norm_headwise

NEG_INF = -2.0e38


def _pick_chunk(seq: int, target: int) -> int:
    """Largest divisor of ``seq`` that is <= target (seqs here are powers of 2)."""
    c = min(seq, target)
    while seq % c:
        c -= 1
    return c


# --------------------------------------------------------------------- #
# core: chunked online-softmax attention (the jnp "flash" path)
# --------------------------------------------------------------------- #
def chunked_attention(
    q: jnp.ndarray,  # (B, Sq, H, D)
    k: jnp.ndarray,  # (B, Sk, K, D)
    v: jnp.ndarray,  # (B, Sk, K, Dv)
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset=0,  # scalar or (B,) position of q[0] in the kv timeline
    kv_valid_len=None,  # scalar: kv positions >= this are masked out
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    causal_block_skip: bool = False,
) -> jnp.ndarray:
    """Returns (B, Sq, H, Dv). fp32 softmax, inputs' dtype output.

    ``causal_block_skip``: iterate only the lower-triangular (q,k) chunk pairs
    (plus the diagonal band) instead of the full grid — halves attention FLOPs
    for causal training at the cost of a slightly more complex schedule.  This
    is a beyond-paper perf option; numerics are identical (masked blocks that
    are skipped contribute exactly zero).
    """
    B, Sq, H, D = q.shape
    _, Sk, K, Dv = v.shape[0], v.shape[1], k.shape[2], v.shape[3]
    G = H // K
    scale = D**-0.5

    q = q.reshape(B, Sq, K, G, D)
    qc = _pick_chunk(Sq, q_chunk)
    kc = _pick_chunk(Sk, kv_chunk)
    nq, nk = Sq // qc, Sk // kc

    q = q.reshape(B, nq, qc, K, G, D).transpose(1, 0, 2, 3, 4, 5)  # (nq,B,qc,K,G,D)
    kb = k.reshape(B, nk, kc, K, D).transpose(1, 0, 2, 3, 4)  # (nk,B,kc,K,D)
    vb = v.reshape(B, nk, kc, K, Dv).transpose(1, 0, 2, 3, 4)

    q_off = jnp.asarray(q_offset)
    if q_off.ndim == 1:
        q_off = q_off[:, None]  # (B,1)

    def kv_step(carry, inp):
        acc, m, l, qi, qpos = carry
        kblk, vblk, ki = inp
        # scores: (B, K, G, qc, kc)
        s = jnp.einsum("bqkgd,bckd->bkgqc", qi, kblk, preferred_element_type=jnp.float32)
        s = s * scale
        kpos = ki * kc + jnp.arange(kc)  # (kc,)
        mask = jnp.ones((qc, kc) if q_off.ndim < 2 else (B, qc, kc), dtype=bool)
        qp = qpos  # (qc,) or (B, qc)
        if causal:
            mask = mask & (kpos[None, :] <= qp[..., :, None])
        if window is not None:
            mask = mask & (kpos[None, :] > qp[..., :, None] - window)
        if kv_valid_len is not None:
            mask = mask & (kpos < kv_valid_len)[None, :]
        if mask.ndim == 2:
            mask = mask[None, :, :]
        s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))  # (B,K,G,qc)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqc,bckv->bqkgv", p.astype(vblk.dtype), vblk,
                        preferred_element_type=jnp.float32)
        acc = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
        return (acc, m_new, l, qi, qpos), None

    def q_block(qi_idx, qi):
        qpos = q_off + qi_idx * qc + jnp.arange(qc)  # (qc,) or (B,qc)
        acc0 = jnp.zeros((B, qc, K, G, Dv), jnp.float32)
        m0 = jnp.full((B, K, G, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, qc), jnp.float32)
        if causal and causal_block_skip:
            # only kv chunks that can contain unmasked positions:
            # k_end <= q_end  ->  ki <= (q_hi // kc)
            n_live = (qi_idx * qc + qc - 1) // kc + 1
            ks = jnp.arange(nk)
            live = ks < n_live

            def masked_step(carry, inp):
                kblk, vblk, ki, is_live = inp
                new_carry, _ = kv_step(carry, (kblk, vblk, ki))
                carry = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(is_live, n, o), new_carry, carry
                )
                return carry, None

            (acc, m, l, _, _), _ = jax.lax.scan(
                masked_step, (acc0, m0, l0, qi, qpos), (kb, vb, ks, live)
            )
        else:
            (acc, m, l, _, _), _ = jax.lax.scan(
                kv_step, (acc0, m0, l0, qi, qpos), (kb, vb, jnp.arange(nk))
            )
        out = acc / jnp.maximum(l, 1e-37).transpose(0, 3, 1, 2)[..., None]
        return out.reshape(B, qc, H, Dv)

    if nq == 1:
        out = q_block(0, q[0])[:, None]
        out = out.reshape(B, 1, qc, H, Dv)
    else:
        out = jax.lax.map(lambda t: q_block(t[0], t[1]), (jnp.arange(nq), q))
        out = out.transpose(1, 0, 2, 3, 4)  # (B,nq,qc,H,Dv)
    return out.reshape(B, Sq, H, Dv).astype(v.dtype)


def decode_attention_xla(
    q: jnp.ndarray,  # (B, 1, H, D)
    k_cache: jnp.ndarray,  # (B, S, K, D)
    v_cache: jnp.ndarray,  # (B, S, K, Dv)
    *,
    cache_index,  # scalar int: last valid position (inclusive)
    window: int | None = None,
) -> jnp.ndarray:
    """Single-token decode against a full cache. Returns (B, 1, H, Dv)."""
    B, S, K, D = k_cache.shape
    H = q.shape[2]
    G = H // K
    Dv = v_cache.shape[-1]
    qg = q.reshape(B, K, G, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache, preferred_element_type=jnp.float32)
    s = s * (D**-0.5)
    pos = jnp.arange(S)
    mask = pos <= cache_index
    if window is not None:
        mask = mask & (pos > cache_index - window)
    s = jnp.where(mask[None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskv->bkgv", w.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, Dv).astype(v_cache.dtype)


# --------------------------------------------------------------------- #
# GQA attention layer
# --------------------------------------------------------------------- #
def init_attention(key, cfg: ModelConfig):
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, cfg.n_heads * hd), dtype=cfg.pdtype),
        "wk": dense_init(ks[1], (d, cfg.n_kv_heads * hd), dtype=cfg.pdtype),
        "wv": dense_init(ks[2], (d, cfg.n_kv_heads * hd), dtype=cfg.pdtype),
        "wo": dense_init(ks[3], (cfg.n_heads * hd, d), dtype=cfg.pdtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), cfg.pdtype)
        p["k_norm"] = jnp.ones((hd,), cfg.pdtype)
    return p


def _project_qkv(p, x, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    hd = cfg.head_dim
    dt = cfg.dtype
    q = (x @ p["wq"].astype(dt)).reshape(B, S, cfg.n_heads, hd)
    k = (x @ p["wk"].astype(dt)).reshape(B, S, cfg.n_kv_heads, hd)
    v = (x @ p["wv"].astype(dt)).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm_headwise(p["q_norm"], q)
        k = rms_norm_headwise(p["k_norm"], k)
    if positions is not None:  # rope (None for whisper-style abs-pos models)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def apply_attention_train(p, x, cfg: ModelConfig, *, window=None, use_rope=True,
                          causal=True, kv=None, block_skip=False):
    """Training/prefill attention. ``kv``: external (B,Skv,d) source for
    cross-attention (whisper decoder); rope is skipped for cross-attn."""
    B, S, _ = x.shape
    positions = jnp.arange(S) if use_rope else None
    if kv is None:
        q, k, v = _project_qkv(p, x, cfg, positions)
    else:
        dt = cfg.dtype
        hd = cfg.head_dim
        q = (x @ p["wq"].astype(dt)).reshape(B, S, cfg.n_heads, hd)
        Skv = kv.shape[1]
        k = (kv @ p["wk"].astype(dt)).reshape(B, Skv, cfg.n_kv_heads, hd)
        v = (kv @ p["wv"].astype(dt)).reshape(B, Skv, cfg.n_kv_heads, hd)
        causal = False
    from repro.kernels import flash_attention_dispatch

    out = flash_attention_dispatch(
        q, k, v, causal=causal, window=window, block_skip=block_skip
    )
    out = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
    return out @ p["wo"].astype(cfg.dtype)


def apply_attention_prefill(p, x, cfg: ModelConfig, *, window=None):
    """Prefill: like train but also returns the populated (k,v) cache,
    leaving one free slot at the end for the next decoded token."""
    B, S, _ = x.shape
    positions = jnp.arange(S)
    q, k, v = _project_qkv(p, x, cfg, positions)
    from repro.kernels import flash_attention_dispatch

    out = flash_attention_dispatch(q, k, v, causal=True, window=window)
    out = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
    return out @ p["wo"].astype(cfg.dtype), {"k": k, "v": v}


@custom_batching.custom_vmap
def update_slice(operand, update, *start):
    """``lax.dynamic_update_slice``.  Batched with the same ``start`` for
    every member (decode under the service's vmap writes each task's row at
    one layer and position), it stays a dynamic-update-slice of the batched
    operand, where JAX's own rule makes a scatter that XLA guards with a
    bounds check and a select on every write."""
    return jax.lax.dynamic_update_slice(operand, update, start)


@update_slice.def_vmap
def _update_slice_vmap(axis_size, in_batched, operand, update, *start):
    operand_b, update_b, *start_b = in_batched
    if any(start_b):  # a start per member: JAX's own rule
        out = jax.vmap(update_slice.fun,
                       in_axes=(0 if operand_b else None,
                                0 if update_b else None,
                                *(0 if b else None for b in start_b)))(
            operand, update, *start)
        return out, True
    if not operand_b:
        operand = jnp.broadcast_to(operand, (axis_size,) + operand.shape)
    if not update_b:
        update = jnp.broadcast_to(update, (axis_size,) + update.shape)
    return update_slice(operand, update, 0, *start), True


def write_row(cache, row, cache_index, layer=None):
    """Writes one token's ``row`` (B, 1, ...) at position ``cache_index`` of
    ``cache``: one layer's (B, S, ...) cache, or with ``layer`` the stacked
    (L, B, S, ...) cache, in place of a copy of that layer's slice.
    Returns (the written cache, that layer's (B, S, ...) view of it).

    On the TPU the stack keeps the sequence axis next to the minor one, the
    layout decode attention reads; left to itself, XLA lays the stack out
    for the row write (batch on the sublanes), and then every layer's
    attention re-tiles its whole slice.  The CPU keeps its default layout,
    which any other would make it copy."""
    row = row.astype(cache.dtype)
    tail = (0,) * (row.ndim - 2)
    if layer is None:
        cache = update_slice(cache, row, 0, cache_index, *tail)
        return cache, cache
    cache = update_slice(cache, row[None], layer, 0, cache_index, *tail)
    n = cache.ndim
    seq_second_minor = Layout((0, 1, *range(3, n - 1), 2, n - 1))
    cache = jax.lax.platform_dependent(
        cache, tpu=lambda c: with_layout_constraint(c, seq_second_minor),
        default=lambda c: c)
    return cache, jax.lax.dynamic_index_in_dim(cache, layer, keepdims=False)


def apply_attention_decode(p, x, cache, cfg: ModelConfig, *, cache_index,
                           layer=None, window=None, kv_cross=None,
                           use_rope=True):
    """One-token decode. x: (B,1,d). cache: {"k","v"}, one layer's (B,S,K,hd)
    or, with ``layer``, the stacked (L,B,S,K,hd); the new token's k/v are
    written at ``cache_index`` (of ``layer``). Returns (out, new_cache)."""
    B = x.shape[0]
    if kv_cross is not None:  # cross-attention: cache is the encoder's kv
        dt = cfg.dtype
        q = (x @ p["wq"].astype(dt)).reshape(B, 1, cfg.n_heads, cfg.head_dim)
        out = decode_attention_xla(
            q, cache["k"], cache["v"], cache_index=cache["k"].shape[1] - 1
        )
        out = out.reshape(B, 1, cfg.n_heads * cfg.head_dim)
        return out @ p["wo"].astype(cfg.dtype), cache

    positions = jnp.full((1,), cache_index, dtype=jnp.int32) if use_rope else None
    q, k, v = _project_qkv(p, x, cfg, positions)
    k_all, k_cache = write_row(cache["k"], k, cache_index, layer)
    v_all, v_cache = write_row(cache["v"], v, cache_index, layer)
    from repro.kernels import decode_attention_dispatch

    out = decode_attention_dispatch(
        q, k_cache, v_cache, cache_index=cache_index, window=window
    )
    out = out.reshape(B, 1, cfg.n_heads * cfg.head_dim)
    return out @ p["wo"].astype(cfg.dtype), {"k": k_all, "v": v_all}


def make_empty_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=None):
    dtype = dtype or cfg.dtype
    shape = (batch, seq_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
