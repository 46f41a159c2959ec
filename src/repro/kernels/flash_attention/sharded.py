"""Tensor-parallel flash attention via shard_map (beyond-paper perf pass).

Attention is embarrassingly parallel over (batch, heads) — GSPMD doesn't
know that inside the blocked online-softmax loops and re-shards the block
carries every iteration (hundreds of GB of all-gathers per train step in the
baseline dry-run).  ``shard_map`` makes the parallelism explicit: each device
runs the *local* flash attention on its (batch-shard, head-shard) with ZERO
collectives inside.

GQA head alignment: with tp devices on the head axis,
  * K >= tp and K % tp == 0: shard kv heads directly,
  * K <  tp and tp % K == 0: duplicate each kv head tp/K times and
    *permute* q heads so every duplicate serves a contiguous slice of its
    own kv head's queries (padding q with zero-heads up to the slice size —
    zero heads attend uniformly to zero values, contribute zero output and
    zero gradient, and are dropped on the way out).

The inner computation is the same ``flash_attention_xla`` custom-vjp, so the
memory-efficient manual backward transposes through shard_map unchanged.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.sharding.hints import current_axes, current_mesh
from .xla import flash_attention_xla


@dataclass(frozen=True)
class HeadPlan:
    tp: int
    Hp: int  # padded/permuted q heads
    Kp: int  # replicated/padded kv heads
    q_src: tuple  # (Hp,) index into original q heads, -1 = zero pad
    kv_src: tuple  # (Kp,) index into original kv heads, -1 = zero pad
    inv: tuple  # (H,) position of original head h in the padded layout


def plan_heads(H: int, K: int, tp: int) -> HeadPlan | None:
    """None if no rearrangement is needed (already divisible).

    NOTE (perf iteration 2, refuted): expressing these expansions as
    pad/broadcast/reshape instead of ``take`` was hypothesized to be
    GSPMD-friendlier; measured the OPPOSITE (qwen3 train collective
    2.1 s -> 4.3 s) because GSPMD reshards reshapes by full replication
    ("involuntary full rematerialization").  The head-index ``take``
    lowers to all-to-alls and wins; keeping it."""
    if H % tp == 0 and K % tp == 0:
        return None
    G = H // K
    if K >= tp:
        if K % tp and H == K:
            # MHA with awkward head count: pad BOTH (zero kv heads are safe)
            Kp = math.ceil(K / tp) * tp
            q_src = tuple(list(range(H)) + [-1] * (Kp - H))
            kv_src = tuple(list(range(K)) + [-1] * (Kp - K))
            inv = tuple(range(H))
            return HeadPlan(tp, Kp, Kp, q_src, kv_src, inv)
        return None
    if tp % K:
        return None
    dup = tp // K
    Gp = math.ceil(G / dup)
    q_src, inv = [], [0] * H
    for j in range(K * dup):
        kv = j // dup
        base = kv * G + (j % dup) * Gp
        for t in range(Gp):
            h = base + t
            if h < (kv + 1) * G and h < H:
                inv[h] = len(q_src)
                q_src.append(h)
            else:
                q_src.append(-1)
    kv_src = tuple(j // dup for j in range(K * dup))
    return HeadPlan(tp, K * dup * Gp, K * dup, tuple(q_src), kv_src,
                    tuple(inv))


def _take_heads(x, src):
    """Gather heads along axis 2 with -1 -> zeros."""
    idx = jnp.asarray([max(s, 0) for s in src])
    out = jnp.take(x, idx, axis=2)
    mask = jnp.asarray([1.0 if s >= 0 else 0.0 for s in src], x.dtype)
    return out * mask[None, None, :, None]


def flash_attention_tp(q, k, v, *, causal=True, window=None,
                       q_chunk=512, kv_chunk=1024):
    """shard_map'd flash attention; falls back to the GSPMD path when no
    mesh is active or the head counts can't be aligned."""
    mesh = current_mesh()
    axes = current_axes()
    B, Sq, H, Dq = q.shape
    K = k.shape[2]
    if mesh is None or axes is None or "model" not in mesh.axis_names:
        return flash_attention_xla(q, k, v, causal, window, q_chunk, kv_chunk)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = sizes.get("model", 1)
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = int(np.prod([sizes[a] for a in dp_axes])) if dp_axes else 1
    if B % dp:
        return flash_attention_xla(q, k, v, causal, window, q_chunk, kv_chunk)

    plan = plan_heads(H, K, tp)
    if plan is None and (H % tp or K % tp):
        return flash_attention_xla(q, k, v, causal, window, q_chunk, kv_chunk)
    spec = P(dp_axes if dp_axes else None, None, "model", None)
    if plan is not None:
        q = _take_heads(q, plan.q_src)
        k = _take_heads(k, plan.kv_src)
        v = _take_heads(v, plan.kv_src)

    def local(q_, k_, v_):
        return flash_attention_xla(q_, k_, v_, causal, window, q_chunk,
                                   kv_chunk)

    out = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                        out_specs=spec, check_vma=False)(q, k, v)
    if plan is not None:
        out = jnp.take(out, jnp.asarray(plan.inv), axis=2)
    return out
