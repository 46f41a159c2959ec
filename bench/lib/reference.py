"""The plain reference's shared pieces and the comparison that decides
``correct``.

Each model family (``bench/models/<family>.py``) computes its own
float32 forward pass (its ``logits``), following the published
description of the architecture with the numbers of the configuration
file as it is run; it may build on :func:`rms`, :func:`rope` and
:func:`fp8_round` here.  It imports nothing of the program and takes
nothing that the program made: its weights are drawn again from the seed.

``quantize`` gives the control: the same pass with every weight matrix
rounded to float8 (e4m3, one scale per output channel), the step below
the bfloat16 that the configurations state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def fp8_round(w, axis):
    """``w`` through float8 e4m3 with one scale per slice along ``axis``
    (the reduction axis of its matmul)."""
    w = w.astype(F32)
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (R, T, heads, hd), rotated halves, positions 0..T-1."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def served_gap(seed: int, family, m, prompts, served, *,
               quantize: bool = False, chunk: int = 0) -> dict:
    """How far the served tokens' logits lie below the reference's best.

    ``prompts`` (R, P) and ``served`` (R, N) are what the program was
    given and returned.  The family's reference runs once over prompt +
    served tokens; the logit at position P-1+j judges served token j.
    Returns ``max_gap`` (the widest gap, the number compared) and
    ``control_gap``, the widest gap of the tokens that the float8 pass
    would put first at the same positions (when ``quantize``).  ``chunk``
    rows at a time (0: all) bounds the reference's memory."""
    prompts, served = np.asarray(prompts), np.asarray(served)
    R, P = prompts.shape
    N = served.shape[1]
    chunk = chunk or R
    gaps, cgaps = [], []
    for r0 in range(0, R, chunk):
        p, s = prompts[r0:r0 + chunk], served[r0:r0 + chunk]
        toks = np.concatenate([p, s[:, :N - 1]], axis=1)
        pos = np.arange(P - 1, P - 1 + N)
        ref = family.logits(seed, m, toks, pos)
        best = ref.max(-1)
        got = jnp.take_along_axis(ref, jnp.asarray(s)[..., None], -1)[..., 0]
        gaps.append(np.asarray(best - got))
        if quantize:
            ctl = family.logits(seed, m, toks, pos, quantize=True)
            pick = jnp.argmax(ctl, -1)
            cg = best - jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
            cgaps.append(np.asarray(cg))
    out = {"max_gap": float(np.max(np.concatenate(gaps))),
           "tokens": int(R * N)}
    if quantize:
        out["control_gap"] = float(np.max(np.concatenate(cgaps)))
    return out
