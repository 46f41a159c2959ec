"""Token-dropping (capacity-factor) mixture-of-experts.

Dispatch/combine are expressed as dense one-hot einsums over
(tokens, experts, capacity) — the canonical TPU formulation (Switch/GLaM):
fully static-shaped, MXU-friendly, and shardable.  Two scale decisions:

* **Routing groups** (``MoEConfig.group_size``): capacity is allocated per
  group of G tokens, so the dispatch one-hot is (groups, G, E, C) with
  C = ceil(cf*k*G/E).  Its size is O(tokens * E * C); per-sequence groups
  (G=4096, E=128) would be 10 TiB for the llama4 train cell vs ~0.8 TiB at
  G=256.  Groups also align with sequence-parallel shards (G = S/TP), so
  routing is shard-local and only the expert exchange crosses devices.

* **Expert parallelism**: experts are pinned to the "model" mesh axis and
  token groups to the data axes; under GSPMD the dispatch einsum then lowers
  to the canonical all-to-all exchange.

``dense_residual`` adds an always-on dense FFN branch (Arctic's dense-MoE
hybrid; also models llama4-maverick's and DeepSeek's shared experts).

``MoEConfig.dropless`` selects the other layer, DeepSeek-V2's routing over
one chip's share of the experts (expert parallelism): the router scores all
``n_experts``, each token keeps its top-k gates (renormalised or not),
and the layer computes only the choices that fall on the
``n_held`` experts from ``held_offset``, sorted by expert into one grouped
matmul, so that no token is dropped whatever the routing.  What the experts
held elsewhere add is left to their chips; on one chip there is no
exchange.  The grouped matmul is the Pallas ``megablox.gmm`` kernel on the
TPU (``jax.lax.ragged_dot`` elsewhere): XLA's own TPU ragged dot drops the
op's name stack, which the device trace reads scopes from.  A layer scan
must not slice the routed expert stacks (the kernel is a custom call, which
fuses no slice, so each layer's experts would be copied every step):
``split_held`` takes them out of the scanned params and ``join_held`` hands
a layer the whole stack and its own index, and the kernel reads only that
layer's groups.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from .common import ModelConfig
from .layers import dense_init, init_dense_mlp, apply_dense_mlp
from repro.sharding.hints import shard_hint


def init_moe(key, cfg: ModelConfig):
    assert cfg.moe is not None
    m = cfg.moe
    d, ff = cfg.d_model, m.d_ff_expert or cfg.d_ff
    ks = jax.random.split(key, 5)
    experts = {
        "wi": jax.vmap(lambda k: dense_init(k, (d, ff), dtype=cfg.pdtype))(
            jax.random.split(ks[0], m.held)),
        "wo": jax.vmap(lambda k: dense_init(k, (ff, d), in_axis_size=ff,
                                            dtype=cfg.pdtype))(
            jax.random.split(ks[1], m.held)),
    }
    if cfg.mlp_act == "swiglu":
        experts["wg"] = jax.vmap(lambda k: dense_init(k, (d, ff), dtype=cfg.pdtype))(
            jax.random.split(ks[2], m.held))
    p = {"router": dense_init(ks[3], (d, m.n_experts), dtype=jnp.float32),
         "experts": experts}
    if m.dense_residual:
        p["residual"] = init_dense_mlp(ks[4], _residual_cfg(cfg))
    return p


def _residual_cfg(cfg: ModelConfig) -> ModelConfig:
    ff = cfg.moe.dense_residual_ff
    return cfg if not ff else cfg.replace(d_ff=ff)


def _gates(probs, m):
    """Top-k of the router's probabilities: (gates, expert ids)."""
    gate, idx = jax.lax.top_k(probs, m.top_k)
    if m.top_k > 1 and m.renormalize:  # mixtral-style
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return gate, idx


def _aux_loss(logits, probs, idx, m):
    """Load-balance and router z losses (fp32)."""
    lead = tuple(range(probs.ndim - 1))
    onehot = jax.nn.one_hot(idx, m.n_experts, dtype=jnp.float32)
    me = jnp.mean(probs, axis=lead)  # mean router prob per expert
    ce = jnp.mean(jnp.sum(onehot, axis=-2), axis=lead)  # assignment frac
    lb_loss = m.load_balance_loss * m.n_experts * jnp.sum(me * ce)
    z = jax.scipy.special.logsumexp(logits, axis=-1)
    return lb_loss + m.router_z_loss * jnp.mean(z * z)


def routing_group_size(cfg: ModelConfig, seq_len: int) -> int:
    g = cfg.moe.group_size or seq_len
    g = min(g, seq_len)
    while seq_len % g:  # groups must tile the sequence
        g -= 1
    return g


def expert_capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    m = cfg.moe
    return max(math.ceil(m.capacity_factor * m.top_k * tokens_per_group
                         / m.n_experts), 1)


def apply_moe(p, x, cfg: ModelConfig):
    """x: (B, S, d). Returns (out, aux_loss)."""
    if cfg.moe.dropless:
        return apply_moe_dropless(p, x, cfg)
    m = cfg.moe
    B, S, d = x.shape
    E, K = m.n_experts, m.top_k
    G = routing_group_size(cfg, S)
    ng = B * (S // G)  # total routing groups
    C = expert_capacity(cfg, G)
    dt = cfg.dtype

    xg = x.reshape(ng, G, d)
    logits = (xg.astype(jnp.float32) @ p["router"].astype(jnp.float32))  # (ng,G,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = _gates(probs, m)  # (ng,G,K)

    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # (ng,G,K,E)
    # choice-major priority: all first choices beat all second choices
    oh_cm = onehot.transpose(0, 2, 1, 3).reshape(ng, K * G, E)
    pos_cm = jnp.cumsum(oh_cm, axis=1) - oh_cm  # position within expert
    pos = pos_cm.reshape(ng, K, G, E).transpose(0, 2, 1, 3)  # (ng,G,K,E)
    keep = (pos < C) * onehot  # (ng,G,K,E)
    pos_oh = jax.nn.one_hot(jnp.sum(pos * onehot, -1), C, dtype=jnp.float32)
    # dispatch (ng,G,E,C) in {0,1}; combine weighted by gate
    dispatch = shard_hint(jnp.einsum("gske,gskc->gsec", keep, pos_oh),
                          "moe_dispatch")
    combine = shard_hint(jnp.einsum("gske,gskc,gsk->gsec", keep, pos_oh, gate),
                         "moe_dispatch")

    xin = jnp.einsum("gsec,gsd->egcd", dispatch.astype(dt), xg)
    xin = shard_hint(xin, "moe_expert_batch")
    wi, wo = p["experts"]["wi"].astype(dt), p["experts"]["wo"].astype(dt)
    if cfg.mlp_act == "swiglu":
        wg = p["experts"]["wg"].astype(dt)
        h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", xin, wg)) * jnp.einsum(
            "egcd,edf->egcf", xin, wi)
    else:
        h = jax.nn.gelu(jnp.einsum("egcd,edf->egcf", xin, wi))
    eout = shard_hint(jnp.einsum("egcf,efd->egcd", h, wo), "moe_expert_batch")
    out = jnp.einsum("egcd,gsec->gsd", eout, combine.astype(dt),
                     preferred_element_type=jnp.float32).astype(dt)
    out = out.reshape(B, S, d)

    aux = _aux_loss(logits, probs, idx, m)
    if m.dense_residual:
        out = out + apply_dense_mlp(p["residual"], x, _residual_cfg(cfg))
    return out, aux


class HeldExperts(NamedTuple):
    """A dropless layer's routed experts as a layer scan hands them over:
    every layer's stacked experts, and this layer's index."""

    stack: Any
    layer: Any


def split_held(block, cfg: ModelConfig):
    """(a stacked block's params without a dropless layer's routed
    experts, those experts or None): the layer scan passes them whole."""
    if cfg.moe is None or not cfg.moe.dropless or "moe" not in block:
        return block, None
    moe = dict(block["moe"])
    experts = moe.pop("experts")
    return dict(block, moe=moe), experts


def join_held(block, experts, layer):
    """One layer's block params, its routed experts given back as
    ``HeldExperts(experts, layer)`` (``experts`` from ``split_held``)."""
    if experts is None:
        return block
    return dict(block, moe=dict(block["moe"],
                                experts=HeldExperts(experts, layer)))


def _gmm_tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """(rows, contraction, columns) of a tile: 128 rows in decode (a held
    expert's few rows share a tile with its neighbours'), 512 in prefill;
    a weight block of 512 x n where 512 divides k, else k x 512 (1.4 MB
    at DeepSeek-V2-Lite's widths)."""
    tm = 128 if m <= 4096 else 512
    return (tm, 512, n) if k % 512 == 0 else (tm, k, min(n, 512))


def grouped_matmul(x, w, sizes, layer):
    """x (M, k), its rows sorted by group; w (L, E, k, n), the experts of L
    layers; sizes (E,): the rows of each of layer ``layer``'s experts.
    Returns (M, n); rows past the groups are not written."""
    L, E = w.shape[:2]
    w = w.reshape(L * E, *w.shape[2:])
    if L > 1:  # only this layer's groups hold rows
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((L * E,), jnp.int32), sizes, (layer * E,))

    def tpu(x, w, sizes):
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        M = x.shape[0]
        tiling = _gmm_tiling(M, *w.shape[1:])
        pad = -M % tiling[0]  # the kernel takes whole row tiles
        if pad:  # rows past the groups
            x = jnp.pad(x, ((0, pad), (0, 0)))
        y = gmm(x, w, sizes, preferred_element_type=x.dtype, tiling=tiling)
        return y[:M] if pad else y

    return jax.lax.platform_dependent(x, w, sizes, tpu=tpu,
                                      default=jax.lax.ragged_dot)


def apply_moe_dropless(p, x, cfg: ModelConfig):
    """The dropless layer over the held experts. x: (B, S, d). Returns
    (out, aux_loss).  ``p["experts"]``: one layer's (E, ...) experts, or
    ``HeldExperts`` from a layer scan."""
    m = cfg.moe
    B, S, d = x.shape
    T, K, E = B * S, m.top_k, m.held
    dt = cfg.dtype
    xt = x.reshape(T, d)
    with jax.named_scope("router"):
        logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = _gates(probs, m)  # (T,K)
        local = idx - m.held_offset
        held = (local >= 0) & (local < E)
        # the T*K choices sorted by held expert, the others (E) last
        key = jnp.where(held, local, E).reshape(T * K)
        order = jnp.argsort(key, stable=True)
        back = jnp.argsort(order)  # choice -> its sorted row
        sizes = jnp.zeros((E + 1,), jnp.int32).at[key].add(1)[:E]
    experts = p["experts"]
    if not isinstance(experts, HeldExperts):
        experts = HeldExperts(
            jax.tree_util.tree_map(lambda a: a[None], experts), 0)
    stack, layer = experts
    with jax.named_scope("experts"):
        def mm(a, name):
            return grouped_matmul(a, stack[name].astype(dt), sizes, layer)

        xs = jnp.take(xt, order // K, axis=0)
        h = jax.nn.silu(mm(xs, "wg")) * mm(xs, "wi")
        ys = mm(h, "wo")
        # rows past the held groups are never written: select, not scale
        y = jnp.take(ys, back, axis=0).reshape(T, K, d)
        y = jnp.where(held[..., None], y.astype(jnp.float32), 0.0)
        out = jnp.sum(y * gate[..., None], axis=1).astype(dt).reshape(B, S, d)
    aux = _aux_loss(logits, probs, idx, m)
    if m.dense_residual:
        with jax.named_scope("shared_experts"):
            out = out + apply_dense_mlp(p["residual"], x, _residual_cfg(cfg))
    return out, aux
