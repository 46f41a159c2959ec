"""Chunked selective-scan kernel (Pallas, TPU target).

The Mamba-1 recurrence  h_t = exp(dt_t·A)·h_{t-1} + dt_t·B_t·x_t  is
processed in sequence chunks with the SSM state resident in VMEM scratch
across the (sequential) chunk grid dimension — the TPU re-tiling of the
CUDA selective-scan: instead of one thread-block per (batch, channel-split)
with warp shuffles, we tile (batch, d_inner-block) across the parallel grid
dims and keep the (block_d, N) state vector in VMEM while streaming
(chunk, block_d) activation tiles from HBM.

VMEM per step: x/dt tiles 2·(chunk=256 × block_d=256)·4B = 512 KiB,
B/C tiles 2·(256×16)·4B = 32 KiB, state (256×16)·4B = 16 KiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref, y_ref, hf_ref,
                 h_scr, *, chunk: int, n_chunks: int):
    cb = pl.program_id(2)

    @pl.when(cb == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    A = a_ref[...].astype(jnp.float32)  # (bd, N)

    def step(t, h):
        xt = x_ref[0, t, :].astype(jnp.float32)  # (bd,)
        dtt = dt_ref[0, t, :].astype(jnp.float32)  # (bd,)
        Bt = b_ref[0, t, :].astype(jnp.float32)  # (N,)
        Ct = c_ref[0, t, :].astype(jnp.float32)  # (N,)
        dA = jnp.exp(dtt[:, None] * A)  # (bd, N)
        h = h * dA + (dtt * xt)[:, None] * Bt[None, :]
        y = jnp.sum(h * Ct[None, :], axis=-1)  # (bd,)
        y_ref[0, t, :] = y.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h

    @pl.when(cb == n_chunks - 1)
    def _final():
        hf_ref[0] = h_scr[...].astype(hf_ref.dtype)


def mamba_scan_pallas(x, dt, A, B, C, h0=None, *, chunk: int | None = None,
                      block_d: int | None = None, interpret: bool = False):
    """x, dt: (b, s, d); A: (d, n); B, C: (b, s, n).
    Returns (y (b,s,d) fp32, h_final (b,d,n) fp32).

    ``chunk``/``block_d`` default to the tuned ``mamba`` config for this
    shape bucket (256/256 when untuned); explicit values degrade to the
    largest valid divisor via typed validation instead of asserting."""
    b, s, d = x.shape
    n = A.shape[-1]
    from repro.tune.cache import best_config
    from repro.tune.space import DEFAULTS, resolve_block

    if chunk is None or block_d is None:
        cfg = best_config("mamba", {"b": b, "s": s, "d": d, "n": n},
                          str(x.dtype), "pallas", DEFAULTS["mamba"])
        chunk = cfg["chunk"] if chunk is None else chunk
        block_d = cfg["block_d"] if block_d is None else block_d
    chunk = resolve_block("chunk", s, chunk)
    block_d = resolve_block("block_d", d, block_d)
    nc, nd = s // chunk, d // block_d
    if h0 is None:
        h0 = jnp.zeros((b, d, n), jnp.float32)

    kernel = functools.partial(_scan_kernel, chunk=chunk, n_chunks=nc)
    x32, dt32 = x.astype(jnp.float32), dt.astype(jnp.float32)
    A32, B32, C32 = (A.astype(jnp.float32), B.astype(jnp.float32),
                     C.astype(jnp.float32))

    y, hf = pl.pallas_call(
        kernel,
        grid=(b, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((1, chunk, block_d), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((block_d, n), lambda bi, di, ci: (di, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, di, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, di, ci: (bi, ci, 0)),
            pl.BlockSpec((1, block_d, n), lambda bi, di, ci: (bi, di, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((1, block_d, n), lambda bi, di, ci: (bi, di, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, d), jnp.float32),
            jax.ShapeDtypeStruct((b, d, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x32, dt32, A32, B32, C32, h0)
    return y, hf
