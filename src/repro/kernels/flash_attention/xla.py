"""Flash attention with MANUAL chunked backward — pure XLA (jnp) version.

Without this, ``jax.grad`` through chunked attention saves every per-chunk
probability block as a scan residual — O(S^2) memory, 17 TB/device at 4k for
a 2B model.  The fix is the flash-attention backward recurrence: save only
(out, logsumexp) from the forward, then re-compute probabilities chunk by
chunk in the backward while accumulating (dq, dk, dv):

    D_i   = rowsum(dO_i * O_i)
    p_ij  = exp(s_ij - lse_i)
    dv_j += p_ij^T dO_i
    ds_ij = p_ij * (dO_i V_j^T - D_i) * scale
    dq_i += ds_ij K_j ;  dk_j += ds_ij^T Q_i

Memory: O(S·H·D) saved + chunk-sized temporaries.  This function is the
training-path attention for the whole framework (the Pallas kernel replaces
the *forward* on TPU; this backward serves both).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38


def _constrain(t, spec_fn):
    """Pin a sharding if the runtime announced mesh axes (no-op otherwise).
    GSPMD replicates ambiguous while-loop carries — without this, the
    backward's dq carry materializes at GLOBAL batch size (20 GiB/device
    for llama4-400b)."""
    from repro.sharding.hints import constrain, current_axes

    axes = current_axes()
    if not axes:
        return t
    from jax.sharding import PartitionSpec as P

    dp = tuple(a for a in ("pod", "data") if a in axes) or None
    m = "model" if "model" in axes else None
    return constrain(t, spec_fn(P, dp, m))


def _pin_batch(t):  # batch-major block tensors: pin batch over dp only
    return _constrain(
        t, lambda P, dp, m: P(dp, *([None] * (t.ndim - 1))))


def _pick_chunk(seq: int, target: int) -> int:
    c = min(seq, target)
    while seq % c:
        c -= 1
    return c


def _mask(s, q_pos, k_pos, causal, window):
    m = None
    if causal:
        m = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        w = k_pos[None, :] > (q_pos[:, None] - window)
        m = w if m is None else (m & w)
    if m is None:
        return s
    return jnp.where(m[None, None, None], s, NEG_INF)


def _fwd_impl(q, k, v, *, causal, window, q_chunk=512, kv_chunk=1024):
    """Returns (out (B,Sq,H,Dv), lse (B,K,G,Sq) fp32)."""
    B, Sq, H, Dq = q.shape
    _, Sk, K, Dv = v.shape
    G = H // K
    scale = Dq**-0.5
    qc = _pick_chunk(Sq, q_chunk)
    kc = _pick_chunk(Sk, kv_chunk)
    nq, nk = Sq // qc, Sk // kc
    qb = q.reshape(B, nq, qc, K, G, Dq).transpose(1, 0, 2, 3, 4, 5)
    kb = k.reshape(B, nk, kc, K, Dq).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, kc, K, Dv).transpose(1, 0, 2, 3, 4)

    def kv_step(carry, inp):
        acc, m, l, qi, qpos = carry
        kblk, vblk, ki = inp
        # barrier: stops XLA from precomputing every block's mask as one
        # stacked (nq x nk x ...) pred tensor outside the loops
        ki = jax.lax.optimization_barrier(ki)
        s = jnp.einsum("bqkgd,bckd->bkgqc", qi, kblk,
                       preferred_element_type=jnp.float32) * scale
        kpos = ki * kc + jnp.arange(kc)
        s = _mask(s, qpos, kpos, causal, window)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqc,bckv->bqkgv", p.astype(vblk.dtype), vblk,
                        preferred_element_type=jnp.float32)
        acc = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
        return (acc, m_new, l, qi, qpos), None

    def q_block(args):
        qi_idx, qi = args
        qi_idx = jax.lax.optimization_barrier(qi_idx)
        qpos = qi_idx * qc + jnp.arange(qc)
        acc0 = jnp.zeros((B, qc, K, G, Dv), jnp.float32)
        m0 = jnp.full((B, K, G, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, qc), jnp.float32)
        (acc, m, l, _, _), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0, qi, qpos), (kb, vb, jnp.arange(nk)))
        out = acc / jnp.maximum(l, 1e-37).transpose(0, 3, 1, 2)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-37))
        return out, lse

    if nq == 1:
        out, lse = q_block((0, qb[0]))
        out = out[None]
        lse = lse[None]
    else:
        out, lse = jax.lax.map(q_block, (jnp.arange(nq), qb))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, Dv).astype(v.dtype)
    # lse: (nq, B, K, G, qc) -> (B, K, G, Sq)
    lse = lse.transpose(1, 2, 3, 0, 4).reshape(B, K, G, Sq)
    return out, lse


def _bwd_impl(q, k, v, out, lse, g, *, causal, window, q_chunk=512,
              kv_chunk=1024):
    B, Sq, H, Dq = q.shape
    _, Sk, K, Dv = v.shape
    G = H // K
    scale = Dq**-0.5
    qc = _pick_chunk(Sq, q_chunk)
    kc = _pick_chunk(Sk, kv_chunk)
    nq, nk = Sq // qc, Sk // kc

    # keep g/out in their storage dtype; convert per-block inside the loops
    qb = q.reshape(B, nq, qc, K, G, Dq).transpose(1, 0, 2, 3, 4, 5)
    gb = g.reshape(B, nq, qc, K, G, Dv).transpose(1, 0, 2, 3, 4, 5)
    ob = out.reshape(B, nq, qc, K, G, Dv).transpose(1, 0, 2, 3, 4, 5)
    kb = k.reshape(B, nk, kc, K, Dq).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, kc, K, Dv).transpose(1, 0, 2, 3, 4)
    lse_q = lse.reshape(B, K, G, nq, qc).transpose(3, 0, 1, 2, 4)  # (nq,B,K,G,qc)

    def _d_block(gi, oi):  # rowsum(dO * O) per q block -> (B,K,G,qc)
        d = jnp.sum(gi.astype(jnp.float32) * oi.astype(jnp.float32), axis=-1)
        return d.transpose(0, 2, 3, 1)

    def _scores(qi, kblk, qpos, kpos, lse_i):
        s = jnp.einsum("bqkgd,bckd->bkgqc", qi, kblk,
                       preferred_element_type=jnp.float32) * scale
        s = _mask(s, qpos, kpos, causal, window)
        return jnp.exp(s - lse_i[..., None])  # (B,K,G,qc,kc)

    # ---- pass A: dq (block carry only; emitted per q block) ----------
    def q_block(args):
        qi_idx, qi, gi, oi, lse_i = args
        qi_idx = jax.lax.optimization_barrier(qi_idx)
        qpos = qi_idx * qc + jnp.arange(qc)
        D_i = _d_block(gi, oi)

        def kv_step(dq_i, inp):
            kblk, vblk, ki = inp
            ki = jax.lax.optimization_barrier(ki)
            kpos = ki * kc + jnp.arange(kc)
            p = _scores(qi, kblk, qpos, kpos, lse_i)
            dp = jnp.einsum("bqkgv,bckv->bkgqc", gi, vblk,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - D_i[..., None]) * scale
            dq_i = dq_i + jnp.einsum("bkgqc,bckd->bqkgd",
                                     ds.astype(kblk.dtype), kblk,
                                     preferred_element_type=jnp.float32)
            return _pin_batch(dq_i), None

        dq0 = _pin_batch(jnp.zeros((B, qc, K, G, Dq), jnp.float32))
        dq_i, _ = jax.lax.scan(kv_step, dq0, (kb, vb, jnp.arange(nk)))
        return dq_i

    dq = jax.lax.map(q_block, (jnp.arange(nq), qb, gb, ob, lse_q))
    dq = dq.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, Dq).astype(q.dtype)

    # ---- pass B: dk, dv (block carries; emitted per kv block) --------
    def kv_block(args):
        ki_idx, kblk, vblk = args
        ki_idx = jax.lax.optimization_barrier(ki_idx)
        kpos = ki_idx * kc + jnp.arange(kc)

        def q_step(carry, inp):
            dk_j, dv_j = carry
            qi_idx, qi, gi, oi, lse_i = inp
            qi_idx = jax.lax.optimization_barrier(qi_idx)
            qpos = qi_idx * qc + jnp.arange(qc)
            D_i = _d_block(gi, oi)
            p = _scores(qi, kblk, qpos, kpos, lse_i)
            dv_j = dv_j + jnp.einsum("bkgqc,bqkgv->bckv", p.astype(gi.dtype),
                                     gi, preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqkgv,bckv->bkgqc", gi, vblk,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - D_i[..., None]) * scale
            dk_j = dk_j + jnp.einsum("bkgqc,bqkgd->bckd", ds.astype(qi.dtype),
                                     qi, preferred_element_type=jnp.float32)
            return (_pin_batch(dk_j), _pin_batch(dv_j)), None

        dk0 = _pin_batch(jnp.zeros((B, kc, K, Dq), jnp.float32))
        dv0 = _pin_batch(jnp.zeros((B, kc, K, Dv), jnp.float32))
        (dk_j, dv_j), _ = jax.lax.scan(
            q_step, (dk0, dv0), (jnp.arange(nq), qb, gb, ob, lse_q))
        return dk_j, dv_j

    dk, dv = jax.lax.map(kv_block, (jnp.arange(nk), kb, vb))
    dk = dk.transpose(1, 0, 2, 3, 4).reshape(B, Sk, K, Dq).astype(k.dtype)
    dv = dv.transpose(1, 0, 2, 3, 4).reshape(B, Sk, K, Dv).astype(v.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_xla(q, k, v, causal=True, window=None, q_chunk=512,
                        kv_chunk=1024):
    out, _ = _fwd_impl(q, k, v, causal=causal, window=window,
                       q_chunk=q_chunk, kv_chunk=kv_chunk)
    return out


def _vjp_fwd(q, k, v, causal, window, q_chunk, kv_chunk):
    out, lse = _fwd_impl(q, k, v, causal=causal, window=window,
                         q_chunk=q_chunk, kv_chunk=kv_chunk)
    return out, (q, k, v, out, lse)


def _vjp_bwd(causal, window, q_chunk, kv_chunk, res, g):
    q, k, v, out, lse = res
    return _bwd_impl(q, k, v, out, lse, g, causal=causal, window=window,
                     q_chunk=q_chunk, kv_chunk=kv_chunk)


flash_attention_xla.defvjp(_vjp_fwd, _vjp_bwd)
