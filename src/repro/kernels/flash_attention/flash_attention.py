"""Flash attention forward kernel (Pallas, TPU target).

Tiling: grid = (batch, q_heads, q_blocks, kv_blocks) with the kv dimension
sequential ("arbitrary"); the online-softmax state (m, l, acc) lives in VMEM
scratch and persists across kv blocks for a fixed (b, h, qb).  Block shapes
default to (128, head_dim) — MXU-aligned (128-multiples) and small enough
that q/k/v/acc tiles fit VMEM comfortably:
    q (128, D) + k (Bk, D) + v (Bk, D) + acc (128, D) fp32
    ~ 4 * 128 * 128 * 4B = 256 KiB  «  16 MiB VMEM (v5e).

GQA is handled in the k/v BlockSpec index_map (q-head h reads kv-head
h * K // H) — no materialized head expansion.  Causal masking skips
fully-masked kv blocks via ``pl.when`` (no FLOPs spent above the diagonal).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38

# (b, h, q-block) grid dims are independent; the last is the sequential
# reduction that carries the online-softmax state in scratch.
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, block_q: int, block_k: int,
                n_kv_blocks: int):
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_prev = m_scr[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * corr
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    if causal:
        # skip blocks entirely above the diagonal: first k row of this block
        # must be <= last q row of this q block
        live = (kb * block_k) <= (qb * block_q + block_q - 1)
        pl.when(live)(_compute)
    else:
        _compute()

    @pl.when(kb == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(l)


def _resolve_blocks(kernel: str, B, Sq, Skv, H, K, D, Dv, dtype,
                    block_q, block_k):
    """Fill ``None`` blocks from the tuning cache (hand-picked defaults
    as fallback), then apply typed validation with largest-valid-divisor
    degradation — a shape-incompatible block can never assert-kill a
    worker mid-sweep, only a malformed one raises (typed
    :class:`~repro.tune.space.KernelConfigError`)."""
    from repro.tune.cache import best_config
    from repro.tune.space import DEFAULTS, resolve_block

    if block_q is None or block_k is None:
        cfg = best_config(
            kernel, {"B": B, "Sq": Sq, "Skv": Skv, "H": H, "K": K,
                     "D": D, "Dv": Dv}, str(dtype), "pallas",
            DEFAULTS[kernel])
        block_q = cfg["block_q"] if block_q is None else block_q
        block_k = cfg["block_k"] if block_k is None else block_k
    return (resolve_block("block_q", Sq, block_q),
            resolve_block("block_k", Skv, block_k))


def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        block_q: int | None = None,
                        block_k: int | None = None,
                        interpret: bool = False, return_lse: bool = False):
    """q: (B, Sq, H, D); k, v: (B, Skv, K, D) with H % K == 0.
    Returns (B, Sq, H, D) in q.dtype [, lse (B, H, Sq) fp32].

    Row statistics (lse, and the backward's Dvec) travel as (B, H, Sq, 1)
    columns: a block's last dim must be a multiple of 128 or the whole
    dim, and its second-to-last a multiple of 8, on the chip.

    ``block_q``/``block_k`` default to the tuned config for this shape
    bucket (``repro.tune`` cache; 128/128 when untuned); explicit values
    degrade to the largest valid divisor if they don't tile the shape."""
    B, Sq, H, D = q.shape
    _, Skv, K, Dv = v.shape
    assert k.shape == (B, Skv, K, D)
    assert H % K == 0
    block_q, block_k = _resolve_blocks("flash_fwd", B, Sq, Skv, H, K, D, Dv,
                                       q.dtype, block_q, block_k)
    nq, nk = Sq // block_q, Skv // block_k
    scale = D**-0.5

    # (B, H, S, D) layout for clean per-(b, h) tiles
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_kv_blocks=nk)

    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qb, kb: (b, h, qb, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qb, kb, K=K, H=H: (b, h * K // H, kb, 0)),
            pl.BlockSpec((1, 1, block_k, Dv),
                         lambda b, h, qb, kb, K=K, H=H: (b, h * K // H, kb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, Dv),
                         lambda b, h, qb, kb: (b, h, qb, 0)),
            _row_spec(block_q),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(qt, kt, vt)
    out = out.transpose(0, 2, 1, 3)
    if return_lse:
        return out, lse[..., 0]
    return out


# --------------------------------------------------------------------- #
# backward kernels: pass A (dq), pass B (dk, dv) — the flash recurrence
#   p = exp(s - lse);  ds = p * (dO V^T - D) * scale
#   dq += ds K;  dk += ds^T Q;  dv += p^T dO     (D = rowsum(dO * O))
# --------------------------------------------------------------------- #
def _bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, d_ref, dq_ref,
                   acc_scr, *, scale, causal, block_q, block_k, n_kv_blocks):
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        qv = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        kv = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        vv = v_ref[0, 0].astype(jnp.float32)  # (bk, dv)
        gv = g_ref[0, 0].astype(jnp.float32)  # (bq, dv)
        s = jax.lax.dot_general(qv, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0])  # lse: (bq, 1)
        dp = jax.lax.dot_general(gv, vv, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - d_ref[0, 0]) * scale
        acc_scr[...] += jax.lax.dot_general(
            ds, kv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when((kb * block_k) <= (qb * block_q + block_q - 1))(_compute)
    else:
        _compute()

    @pl.when(kb == n_kv_blocks - 1)
    def _finalize():
        dq_ref[0, 0] = acc_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, d_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                    block_q, block_k, n_q_blocks, G):
    kb = pl.program_id(2)
    qb = pl.program_id(3)

    @pl.when(qb == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute():
        kv = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        vv = v_ref[0, 0].astype(jnp.float32)  # (bk, dv)
        for g in range(G):  # the G query heads served by this kv head
            qv = q_ref[0, g].astype(jnp.float32)  # (bq, d)
            gv = g_ref[0, g].astype(jnp.float32)  # (bq, dv)
            s = jax.lax.dot_general(qv, kv, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if causal:
                q_pos = qb * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                k_pos = kb * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(k_pos <= q_pos, s, NEG_INF)
            p = jnp.exp(s - lse_ref[0, g])  # (bq, bk)
            dv_scr[...] += jax.lax.dot_general(
                p, gv, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(gv, vv, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - d_ref[0, g]) * scale
            dk_scr[...] += jax.lax.dot_general(
                ds, qv, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if causal:
        # skip q blocks entirely above the diagonal for this kv block
        pl.when((kb * block_k) <= (qb * block_q + block_q - 1))(_compute)
    else:
        _compute()

    @pl.when(qb == n_q_blocks - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, g, *, causal=True, block_q=None,
                        block_k=None, interpret=False):
    """Backward kernels. lse: (B,H,Sq) fp32 from the forward.
    Returns (dq, dk, dv) in input dtypes.  Blocks default to the tuned
    ``flash_bwd`` config (the backward's balance differs from the
    forward's — the dkv pass loads G query-head tiles per step)."""
    B, Sq, H, D = q.shape
    _, Skv, K, Dv = v.shape
    G = H // K
    block_q, block_k = _resolve_blocks("flash_bwd", B, Sq, Skv, H, K, D, Dv,
                                       q.dtype, block_q, block_k)
    nq, nk = Sq // block_q, Skv // block_k
    scale = D**-0.5

    qt = q.transpose(0, 2, 1, 3)  # (B,H,Sq,D)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    gt = g.transpose(0, 2, 1, 3)
    Dvec = jnp.sum(gt.astype(jnp.float32)
                   * out.transpose(0, 2, 1, 3).astype(jnp.float32), axis=-1,
                   keepdims=True)  # (B, H, Sq, 1)
    lse = lse[..., None]

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_kv_blocks=nk)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qb, kb: (b, h, qb, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qb, kb, K=K, H=H: (b, h * K // H, kb, 0)),
            pl.BlockSpec((1, 1, block_k, Dv),
                         lambda b, h, qb, kb, K=K, H=H: (b, h * K // H, kb, 0)),
            pl.BlockSpec((1, 1, block_q, Dv), lambda b, h, qb, kb: (b, h, qb, 0)),
            _row_spec(block_q),
            _row_spec(block_q),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qb, kb: (b, h, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(qt, kt, vt, gt, lse, Dvec)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_q_blocks=nq, G=G)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(B, K, nk, nq),
        in_specs=[
            # G query heads of this kv head: block over the H axis
            pl.BlockSpec((1, G, block_q, D),
                         lambda b, kv, kb, qb: (b, kv, qb, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, kv, kb, qb: (b, kv, kb, 0)),
            pl.BlockSpec((1, 1, block_k, Dv),
                         lambda b, kv, kb, qb: (b, kv, kb, 0)),
            pl.BlockSpec((1, G, block_q, Dv),
                         lambda b, kv, kb, qb: (b, kv, qb, 0)),
            pl.BlockSpec((1, G, block_q, 1),
                         lambda b, kv, kb, qb: (b, kv, qb, 0)),
            pl.BlockSpec((1, G, block_q, 1),
                         lambda b, kv, kb, qb: (b, kv, qb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, kv, kb, qb: (b, kv, kb, 0)),
            pl.BlockSpec((1, 1, block_k, Dv),
                         lambda b, kv, kb, qb: (b, kv, kb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, K, Skv, D), k.dtype),
            jax.ShapeDtypeStruct((B, K, Skv, Dv), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, Dv), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(qt, kt, vt, gt, lse, Dvec)
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))


def _row_spec(block_q):
    """(1, 1, block_q, 1) tile of a (B, H, Sq, 1) row statistic."""
    return pl.BlockSpec((1, 1, block_q, 1), lambda b, h, qb, kb: (b, h, qb, 0))
