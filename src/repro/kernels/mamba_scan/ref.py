"""Pure-jnp oracle for the chunked selective scan.

Recurrence (per batch b, channel d, state n):
    h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t * x_t
    y_t = sum_n C_t[n] * h_t[n]

Outer ``lax.scan`` over sequence chunks carries the state; inside a chunk the
linear recurrence is solved with ``lax.associative_scan``.  Everything is
fp32 (SSM states are numerically delicate in bf16).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _chunk_size(seq: int, target: int = 256) -> int:
    c = min(seq, target)
    while seq % c:
        c -= 1
    return c


def mamba_scan_ref(x, dt, A, B, C, h0=None, chunk: int | None = None):
    """x, dt: (b,s,d); A: (d,n); B, C: (b,s,n).

    Returns (y: (b,s,d) fp32, h_final: (b,d,n) fp32).
    """
    b, s, d = x.shape
    n = A.shape[-1]
    x, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    A, B, C = A.astype(jnp.float32), B.astype(jnp.float32), C.astype(jnp.float32)
    if chunk is None:
        c = _chunk_size(s)
    else:
        # typed validation + largest-divisor fallback: a tuned chunk from
        # a bucketed cache entry may not divide this exact s
        from repro.tune.space import resolve_block

        c = resolve_block("chunk", s, chunk)
    nc = s // c

    if h0 is None:
        h0 = jnp.zeros((b, d, n), jnp.float32)

    def combine(left, right):
        aL, bL = left
        aR, bR = right
        return aL * aR, bL * aR + bR

    def _pin_d(t, d_axis):
        """Keep d_inner sharded over 'model' through the scan — GSPMD
        otherwise gathers every (b, chunk, d_inner, n) intermediate to
        full d_inner in f32 (275 GB/step on falcon-mamba train)."""
        from repro.sharding.hints import constrain, current_axes

        axes = current_axes()
        if not axes or "model" not in axes:
            return t
        from jax.sharding import PartitionSpec as P

        dp = tuple(a for a in ("pod", "data") if a in axes) or None
        spec = [None] * t.ndim
        spec[0] = dp
        spec[d_axis] = "model"
        return constrain(t, P(*spec))

    def chunk_step(h, inp):
        xc, dtc, Bc, Cc = inp  # (b,c,d), (b,c,d), (b,c,n), (b,c,n)
        h = _pin_d(h, 1)
        dA = _pin_d(jnp.exp(dtc[..., None] * A), 2)  # (b,c,d,n)
        dBx = _pin_d((dtc * xc)[..., None] * Bc[:, :, None, :], 2)
        accA, accB = jax.lax.associative_scan(combine, (dA, dBx), axis=1)
        h_all = _pin_d(accA * h[:, None] + accB, 2)  # (b,c,d,n)
        y = jnp.einsum("bcdn,bcn->bcd", h_all, Cc)
        return h_all[:, -1], y

    def _pin_xs(t):  # (nc, b, c, d): keep d_inner sharded through the
        # reshape/transpose
        from repro.sharding.hints import constrain, current_axes

        axes = current_axes()
        if not axes or "model" not in axes or t.shape[-1] != d:
            return t
        from jax.sharding import PartitionSpec as P

        dp = tuple(a for a in ("pod", "data") if a in axes) or None
        return constrain(t, P(None, dp, None, "model"))

    xs = (
        _pin_xs(x.reshape(b, nc, c, d).transpose(1, 0, 2, 3)),
        _pin_xs(dt.reshape(b, nc, c, d).transpose(1, 0, 2, 3)),
        B.reshape(b, nc, c, n).transpose(1, 0, 2, 3),
        C.reshape(b, nc, c, n).transpose(1, 0, 2, 3),
    )
    h_final, ys = jax.lax.scan(chunk_step, h0, xs)
    y = ys.transpose(1, 0, 2, 3).reshape(b, s, d)
    return y, h_final


def mamba_scan_naive(x, dt, A, B, C, h0=None):
    """Step-by-step sequential reference (slow; used to validate the chunked
    oracle itself in tests)."""
    b, s, d = x.shape
    n = A.shape[-1]
    x, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    A, B, C = A.astype(jnp.float32), B.astype(jnp.float32), C.astype(jnp.float32)
    h = jnp.zeros((b, d, n), jnp.float32) if h0 is None else h0

    def step(h, inp):
        xt, dtt, Bt, Ct = inp
        dA = jnp.exp(dtt[..., None] * A)
        h = h * dA + (dtt * xt)[..., None] * Bt[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, Ct)
        return h, y

    xs = (x.transpose(1, 0, 2), dt.transpose(1, 0, 2),
          B.transpose(1, 0, 2), C.transpose(1, 0, 2))
    h, ys = jax.lax.scan(step, h, xs)
    return ys.transpose(1, 0, 2), h
