"""Block assembly and the scan-over-repeats layer stack.

A model is ``cfg.pattern`` (a short tuple of BlockSpec) repeated
``cfg.n_repeats`` times.  Parameters of each pattern position are stacked
along a leading (n_repeats,) axis and the forward pass is a single
``lax.scan`` — HLO stays O(|pattern|) for 72-layer models.  The
``cfg.first_dense_layers`` leading (attention, dense MLP) layers, stacked
under ``lead`` in params and caches, run before the scan, one by one.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import BlockSpec, ModelConfig
from . import attention as attn_mod
from . import mla as mla_mod
from . import mamba as mamba_mod
from .layers import apply_dense_mlp, apply_norm, init_dense_mlp, init_norm
from .moe import apply_moe, init_moe, join_held, split_held


LEAD = BlockSpec(mixer="attn", mlp="dense")


# --------------------------------------------------------------------- #
# single block
# --------------------------------------------------------------------- #
def init_block(key, cfg: ModelConfig, spec: BlockSpec):
    k1, k2 = jax.random.split(key)
    p = {}
    if spec.mixer == "attn":
        p["mixer_norm"] = init_norm(cfg)
        p["attn"] = (mla_mod.init_mla(k1, cfg) if cfg.attention == "mla"
                     else attn_mod.init_attention(k1, cfg))
    elif spec.mixer == "mamba":
        p["mixer_norm"] = init_norm(cfg)
        p["mamba"] = mamba_mod.init_mamba(k1, cfg)
    if spec.mlp == "dense":
        p["mlp_norm"] = init_norm(cfg)
        p["mlp"] = init_dense_mlp(k2, cfg)
    elif spec.mlp == "moe":
        p["mlp_norm"] = init_norm(cfg)
        p["moe"] = init_moe(k2, cfg)
    return p


def _window_for(cfg: ModelConfig, spec: BlockSpec, long_context: bool):
    if spec.window is not None:
        return spec.window
    if long_context and spec.mixer == "attn" and cfg.long_context_window:
        return cfg.long_context_window
    return None


def apply_block_train(p, x, cfg: ModelConfig, spec: BlockSpec, *,
                      long_context=False, use_rope=True, causal=True,
                      block_skip=False):
    aux = jnp.zeros((), jnp.float32)
    if spec.mixer == "attn":
        h = apply_norm(p["mixer_norm"], x, cfg)
        if cfg.attention == "mla":
            with jax.named_scope("mla"):
                h = mla_mod.apply_mla_train(p["attn"], h, cfg)
        else:
            h = attn_mod.apply_attention_train(
                p["attn"], h, cfg, window=_window_for(cfg, spec, long_context),
                use_rope=use_rope, causal=causal, block_skip=block_skip)
        x = x + h
    elif spec.mixer == "mamba":
        h = apply_norm(p["mixer_norm"], x, cfg)
        x = x + mamba_mod.apply_mamba_train(p["mamba"], h, cfg)
    if spec.mlp == "dense":
        h = apply_norm(p["mlp_norm"], x, cfg)
        x = x + apply_dense_mlp(p["mlp"], h, cfg)
    elif spec.mlp == "moe":
        h = apply_norm(p["mlp_norm"], x, cfg)
        h, a = apply_moe(p["moe"], h, cfg)
        x = x + h
        aux = aux + a
    return x, aux


def init_block_cache(cfg: ModelConfig, spec: BlockSpec, batch: int, seq_len: int):
    c = {}
    if spec.mixer == "attn":
        c["attn"] = (mla_mod.make_empty_mla_cache(cfg, batch, seq_len)
                     if cfg.attention == "mla"
                     else attn_mod.make_empty_cache(cfg, batch, seq_len))
    elif spec.mixer == "mamba":
        c["mamba"] = mamba_mod.make_empty_mamba_state(cfg, batch)
    return c


def apply_block_prefill(p, x, cfg: ModelConfig, spec: BlockSpec, *,
                        seq_budget: int, long_context=False):
    """Like train but returns the cache. ``seq_budget``: cache length to
    allocate (>= S; extra slots for subsequent decode)."""
    cache = {}
    if spec.mixer == "attn":
        h = apply_norm(p["mixer_norm"], x, cfg)
        if cfg.attention == "mla":
            with jax.named_scope("mla"):
                h, kv = mla_mod.apply_mla_prefill(p["attn"], h, cfg)
            pad = seq_budget - x.shape[1]
            kv = jax.tree_util.tree_map(
                lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)), kv)
        else:
            h, kv = attn_mod.apply_attention_prefill(
                p["attn"], h, cfg, window=_window_for(cfg, spec, long_context))
            pad = seq_budget - x.shape[1]
            kv = jax.tree_util.tree_map(
                lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)), kv)
        cache["attn"] = kv
        x = x + h
    elif spec.mixer == "mamba":
        h = apply_norm(p["mixer_norm"], x, cfg)
        # prefill for SSM: run the train path then recompute the final state
        hh = h
        di = cfg.d_inner
        xz = hh @ p["mamba"]["in_proj"].astype(cfg.dtype)
        xin, z = xz[..., :di], xz[..., di:]
        xin_c, conv_tail = mamba_mod._causal_conv(p["mamba"], xin, cfg)
        xin_c = jax.nn.silu(xin_c)
        dt, Bm, Cm = mamba_mod._ssm_inputs(p["mamba"], xin_c, cfg)
        A = -jnp.exp(p["mamba"]["A_log"])
        from repro.kernels import mamba_scan_dispatch

        y, h_final = mamba_scan_dispatch(xin_c.astype(jnp.float32), dt, A, Bm, Cm)
        y = y + xin_c.astype(jnp.float32) * p["mamba"]["D"]
        y = y.astype(cfg.dtype) * jax.nn.silu(z)
        x = x + y @ p["mamba"]["out_proj"].astype(cfg.dtype)
        cache["mamba"] = {"conv": conv_tail, "ssm": h_final}
    if spec.mlp == "dense":
        h = apply_norm(p["mlp_norm"], x, cfg)
        x = x + apply_dense_mlp(p["mlp"], h, cfg)
    elif spec.mlp == "moe":
        h = apply_norm(p["mlp_norm"], x, cfg)
        h, _ = apply_moe(p["moe"], h, cfg)
        x = x + h
    return x, cache


def apply_block_decode(p, x, cache, cfg: ModelConfig, spec: BlockSpec, *,
                       layer, cache_index, long_context=False):
    """One token through one block. ``cache``: this pattern position's
    stacked cache (n_repeats leading axis); only ``layer``'s new row
    (attention) or state (mamba) is written into it."""
    if spec.mixer == "attn":
        h = apply_norm(p["mixer_norm"], x, cfg)
        if cfg.attention == "mla":
            with jax.named_scope("mla"):
                h, kv = mla_mod.apply_mla_decode(
                    p["attn"], h, cache["attn"], cfg, cache_index=cache_index,
                    layer=layer)
        else:
            h, kv = attn_mod.apply_attention_decode(
                p["attn"], h, cache["attn"], cfg, cache_index=cache_index,
                layer=layer, window=_window_for(cfg, spec, long_context))
        cache = dict(cache, attn=kv)
        x = x + h
    elif spec.mixer == "mamba":
        h = apply_norm(p["mixer_norm"], x, cfg)
        st = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
            cache["mamba"])
        h, st = mamba_mod.apply_mamba_decode(p["mamba"], h, st, cfg)
        st = jax.tree_util.tree_map(
            lambda a, s: attn_mod.update_slice(
                a, s[None].astype(a.dtype), layer, *(0,) * s.ndim),
            cache["mamba"], st)
        cache = dict(cache, mamba=st)
        x = x + h
    if spec.mlp == "dense":
        h = apply_norm(p["mlp_norm"], x, cfg)
        x = x + apply_dense_mlp(p["mlp"], h, cfg)
    elif spec.mlp == "moe":
        h = apply_norm(p["mlp_norm"], x, cfg)
        h, _ = apply_moe(p["moe"], h, cfg)
        x = x + h
    return x, cache


# --------------------------------------------------------------------- #
# stacked repeats
# --------------------------------------------------------------------- #
def init_blocks(key, cfg: ModelConfig):
    out = {}
    for i, spec in enumerate(cfg.pattern):
        keys = jax.random.split(jax.random.fold_in(key, i), cfg.n_repeats)
        out[f"b{i}"] = jax.vmap(lambda k, s=spec: init_block(k, cfg, s))(keys)
    if cfg.first_dense_layers:
        keys = jax.random.split(jax.random.fold_in(key, len(cfg.pattern)),
                                cfg.first_dense_layers)
        out["lead"] = jax.vmap(lambda k: init_block(k, cfg, LEAD))(keys)
    return out


def _split_lead(tree):
    """(the scanned repeats' part of ``tree``, its ``lead`` stack or None)."""
    if "lead" not in tree:
        return tree, None
    rest = dict(tree)
    return rest, rest.pop("lead")


def _lead_layer(lead, i: int):
    return jax.tree_util.tree_map(lambda a: a[i], lead)


def _split_held(params, cfg: ModelConfig):
    """(``params`` without what the layer scan must not slice, that part by
    pattern position or None): see ``moe.split_held``."""
    rest, held = {}, {}
    for name, block in params.items():
        rest[name], part = split_held(block, cfg)
        if part is not None:
            held[name] = part
    return (rest, held) if held else (params, None)


def _join_held(layer_params, held, layer):
    return {name: join_held(p, held.get(name), layer)
            for name, p in layer_params.items()}


def _scan_xs(params, held, cfg: ModelConfig):
    """The layer scan's ``xs``: the stacked params, and each layer's index
    where a part of them is held whole."""
    return params if held is None else (params, jnp.arange(cfg.n_repeats))


def _scan_step(inp, held):
    """One layer's params from the layer scan's ``xs``."""
    if held is None:
        return inp
    layer_params, layer = inp
    return _join_held(layer_params, held, layer)


def apply_blocks_train(params, x, cfg: ModelConfig, *, long_context=False,
                       use_rope=True, causal=True, block_skip=False):
    from repro.sharding.hints import shard_hint

    params, lead = _split_lead(params)
    for i in range(cfg.first_dense_layers):
        x, _ = apply_block_train(_lead_layer(lead, i), x, cfg, LEAD,
                                 long_context=long_context, use_rope=use_rope,
                                 causal=causal, block_skip=block_skip)

    params, held = _split_held(params, cfg)

    def body(carry, inp):
        x, aux = carry
        layer_params = _scan_step(inp, held)
        # pin the layer-boundary (remat-saved) activation layout; the
        # barrier also stops XLA from hoisting dtype converts of the whole
        # saved stack out of the backward loop (a 2x-3x peak-memory bug).
        x = shard_hint(x, "activations")
        for i, spec in enumerate(cfg.pattern):
            x, a = apply_block_train(
                layer_params[f"b{i}"], x, cfg, spec, long_context=long_context,
                use_rope=use_rope, causal=causal, block_skip=block_skip)
            aux = aux + a
        return (x, aux), None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               _scan_xs(params, held, cfg))
    return x, aux


def init_caches(cfg: ModelConfig, batch: int, seq_len: int):
    """Stacked (n_repeats leading axis) cache pytree."""
    def one(spec, n):
        c = init_block_cache(cfg, spec, batch, seq_len)
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), c)
    out = {f"b{i}": one(spec, cfg.n_repeats)
           for i, spec in enumerate(cfg.pattern)}
    if cfg.first_dense_layers:
        out["lead"] = one(LEAD, cfg.first_dense_layers)
    return out


def apply_blocks_prefill(params, x, cfg: ModelConfig, *, seq_budget,
                         long_context=False):
    params, lead = _split_lead(params)
    lead_caches = []
    for i in range(cfg.first_dense_layers):
        x, c = apply_block_prefill(_lead_layer(lead, i), x, cfg, LEAD,
                                   seq_budget=seq_budget,
                                   long_context=long_context)
        lead_caches.append(c)

    params, held = _split_held(params, cfg)

    def body(x, inp):
        layer_params = _scan_step(inp, held)
        caches = {}
        for i, spec in enumerate(cfg.pattern):
            x, c = apply_block_prefill(layer_params[f"b{i}"], x, cfg, spec,
                                       seq_budget=seq_budget,
                                       long_context=long_context)
            caches[f"b{i}"] = c
        return x, caches

    x, caches = jax.lax.scan(body, x, _scan_xs(params, held, cfg))
    if lead_caches:
        caches["lead"] = jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                                *lead_caches)
    return x, caches


def apply_blocks_decode(params, x, caches, cfg: ModelConfig, *, cache_index,
                        long_context=False):
    """The layer scan carries the stacked caches and each layer writes only
    its new token's row, so a step moves no whole cache slice (the stack as
    the scan's ``xs``/``ys`` would be read and written whole every step)."""
    params, lead = _split_lead(params)
    caches, lead_cache = _split_lead(caches)
    for i in range(cfg.first_dense_layers):
        x, lead_cache = apply_block_decode(
            _lead_layer(lead, i), x, lead_cache, cfg, LEAD, layer=i,
            cache_index=cache_index, long_context=long_context)
    params, held = _split_held(params, cfg)

    def body(carry, inp):
        x, caches = carry
        layer_params, layer = inp
        if held is not None:
            layer_params = _join_held(layer_params, held, layer)
        caches = dict(caches)
        for i, spec in enumerate(cfg.pattern):
            x, caches[f"b{i}"] = apply_block_decode(
                layer_params[f"b{i}"], x, caches[f"b{i}"], cfg, spec,
                layer=layer, cache_index=cache_index,
                long_context=long_context)
        return (x, caches), None

    (x, caches), _ = jax.lax.scan(body, (x, caches),
                                  (params, jnp.arange(cfg.n_repeats)))
    if lead_cache is not None:
        caches = dict(caches, lead=lead_cache)
    return x, caches
