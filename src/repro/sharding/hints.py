"""Sharding-constraint hints that models can emit without knowing the mesh.

Model code calls ``shard_hint(x, kind)``.  If the runtime has announced mesh
axes (``with mesh_axes(("pod","data","model")):``), a
``with_sharding_constraint`` is applied; otherwise (single-device smoke
tests) it is a no-op.  This keeps the model definitions mesh-agnostic while
letting the launcher pin the layouts that matter (vocab-sharded logits,
batch-sharded activations).
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import PartitionSpec as P

_ctx = threading.local()


def current_axes() -> tuple[str, ...] | None:
    return getattr(_ctx, "axes", None)


def current_mesh():
    """The ambient physical mesh (``with mesh:``), or None."""
    from jax._src.mesh import thread_resources

    mesh = thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


@contextlib.contextmanager
def mesh_axes(axes):
    prev = getattr(_ctx, "axes", None)
    _ctx.axes = tuple(axes) if axes else None
    try:
        yield
    finally:
        _ctx.axes = prev


def _dp(axes):
    dp = tuple(a for a in ("pod", "data") if a in axes)
    return dp if dp else None


def spec_for(kind: str, axes, ndim: int) -> P:
    dp = _dp(axes)
    model = "model" if "model" in axes else None
    if kind == "activations":  # (B, S, d) — sequence-parallel over "model"
        # (Megatron-SP): layer-boundary activations & their remat stack
        # shard the sequence dim across the TP axis; GSPMD re-gathers
        # around attention/matmuls as needed.
        return P(dp, model, None)
    if kind == "logits":  # (B, S, V) or (B, V)
        if ndim == 2:
            return P(dp, model)
        return P(dp, None, model)
    if kind == "batch_tokens":  # (B, S)
        return P(dp, None)
    if kind == "moe_dispatch":  # (groups, G, E, C): groups over dp, EP over model
        return P(dp, None, model, None)
    if kind == "moe_expert_batch":  # (E, groups, C, d): EP over model
        return P(model, dp, None, None)
    raise KeyError(kind)


def constrain(x, spec: P):
    """``with_sharding_constraint``, except inside a ``shard_map`` body:
    there the mesh axes are manual, each value is one device's shard, and
    there is no layout left to pin."""
    if jax.sharding.get_abstract_mesh().manual_axes:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def shard_hint(x, kind: str):
    axes = current_axes()
    if not axes:
        return x
    return constrain(x, spec_for(kind, axes, x.ndim))
