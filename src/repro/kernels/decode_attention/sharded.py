"""Sequence-parallel flash-decode via shard_map (perf iteration 3).

The decode cache is laid out (batch over dp, SEQUENCE over "model"); the
baseline GSPMD lowering of one-token attention against it materializes
full-length f32 score tensors and re-shards them (llama4 decode_32k:
21.3 GiB peak, collective 70x compute).  Here each device computes the
flash-decode partial over its LOCAL cache chunk and the partials merge with
an online-softmax reduction over the "model" axis — three tiny psums of
(B, H[, D]) instead of any full-length exchange:

    m_g   = pmax(m_loc)
    l_g   = psum(l_loc * exp(m_loc - m_g))
    out   = psum(acc_loc * exp(m_loc - m_g)) / l_g
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.sharding.hints import current_axes, current_mesh

NEG_INF = -2.0e38


def _local_partials(q, k, v, *, start, cache_index, window):
    """q: (B,1,H,D); k/v: (B,Sl,K,D) local chunk beginning at ``start``.
    Returns (acc (B,H,Dv), m (B,H), l (B,H)) fp32 partials."""
    B, Sl, K, D = k.shape
    H = q.shape[2]
    G = H // K
    Dv = v.shape[-1]
    qg = q.reshape(B, K, G, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k,
                   preferred_element_type=jnp.float32) * (D**-0.5)
    pos = start + jnp.arange(Sl)
    mask = pos <= cache_index
    if window is not None:
        mask = mask & (pos > cache_index - window)
    s = jnp.where(mask[None, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)  # (B,K,G)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgs,bskv->bkgv", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return (acc.reshape(B, H, Dv), m.reshape(B, H), l.reshape(B, H))


def decode_attention_tp(q, k_cache, v_cache, *, cache_index, window=None):
    """Falls back to the GSPMD path when no mesh/axes are active."""
    mesh = current_mesh()
    axes = current_axes()
    B, S, K, D = k_cache.shape
    H = q.shape[2]
    from repro.models.attention import decode_attention_xla

    if mesh is None or axes is None or "model" not in mesh.axis_names:
        return decode_attention_xla(q, k_cache, v_cache,
                                    cache_index=cache_index, window=window)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = sizes.get("model", 1)
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = int(np.prod([sizes[a] for a in dp_axes])) if dp_axes else 1
    if B % dp or S % tp:
        return decode_attention_xla(q, k_cache, v_cache,
                                    cache_index=cache_index, window=window)
    S_loc = S // tp
    bspec = dp_axes if dp_axes else None
    q_spec = P(bspec, None, None, None)
    kv_spec = P(bspec, "model", None, None)
    idx_spec = P()

    def local(q_, k_, v_, ci_):
        start = jax.lax.axis_index("model") * S_loc
        acc, m, l = _local_partials(q_, k_, v_, start=start,
                                    cache_index=ci_, window=window)
        m_g = jax.lax.pmax(m, "model")
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, "model")
        acc_g = jax.lax.psum(acc * corr[..., None], "model")
        out = acc_g / jnp.maximum(l_g, 1e-37)[..., None]
        return out[:, None].astype(v_.dtype)  # (B,1,H,Dv)

    ci = jnp.asarray(cache_index, jnp.int32)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(q_spec, kv_spec, kv_spec, idx_spec),
                         out_specs=q_spec, check_vma=False)(
                             q, k_cache, v_cache, ci)
