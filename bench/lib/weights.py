"""Random weights from the seed, made by the benchmark and not the program.

Every leaf is drawn from its own key, ``fold_in(fold_in(seed key, leaf
id), layer)``, so the whole stack (one jitted call on the device, for
the program) and one layer at a time (for the reference, after the
program's state is freed) give the same numbers.

The layout is the program's (``repro.models.lm``): stacked layers under
``blocks/b0``, weights as ``(in, out)`` matrices, a tied embedding table.
:func:`check_layout` holds the program's own parameter shapes against
it, so a change of layout fails loudly instead of running other weights.

The program's block has no embedding, residual or logit scalars and a
fixed RMSNorm eps; :func:`program_params` folds a configuration's own
(MiniCPM's ``scale_emb``, ``scale_depth``, ``dim_model_base`` and eps
1e-5) into the weights it hands the program, so that the program
computes the configuration's function (:func:`folds`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .spec import Model

# leaf id -> (path, kind); the ids are part of the weights' definition
LAYER_LEAVES = (
    ("mixer_norm/scale", "norm"),
    ("attn/wq", "matrix"),
    ("attn/wk", "matrix"),
    ("attn/wv", "matrix"),
    ("attn/wo", "matrix"),
    ("attn/q_norm", "norm"),
    ("attn/k_norm", "norm"),
    ("mlp_norm/scale", "norm"),
    ("mlp/wi", "matrix"),
    ("mlp/wg", "matrix"),
    ("mlp/wo", "matrix"),
)
EMBED_ID, FINAL_NORM_ID = 100, 101
EMBED_STD = 0.02
NORM_STD = 0.1
PROGRAM_RMS_EPS = 1e-6  # repro.models.layers.apply_norm


def seed_words(seed: int) -> jnp.ndarray:
    """A seed of any size as two uint32 words (jit argument: one compile
    serves every seed)."""
    return jnp.array([seed % 2**31, seed // 2**31], jnp.uint32)


def _base_key(words):
    key = jax.random.PRNGKey(0)
    return jax.random.fold_in(jax.random.fold_in(key, words[0]), words[1])


def layer_shapes(m: Model) -> dict[str, tuple]:
    d, hd = m.hidden_size, m.head_dim
    shapes = {
        "mixer_norm/scale": (d,),
        "attn/wq": (d, m.num_attention_heads * hd),
        "attn/wk": (d, m.num_key_value_heads * hd),
        "attn/wv": (d, m.num_key_value_heads * hd),
        "attn/wo": (m.num_attention_heads * hd, d),
        "mlp_norm/scale": (d,),
        "mlp/wi": (d, m.intermediate_size),
        "mlp/wg": (d, m.intermediate_size),
        "mlp/wo": (m.intermediate_size, d),
    }
    if m.qk_norm:
        shapes["attn/q_norm"] = (hd,)
        shapes["attn/k_norm"] = (hd,)
    return shapes


def _draw(key, shape, kind, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        return (1.0 + NORM_STD * z).astype(dtype)
    return (z * shape[0] ** -0.5).astype(dtype)


def layer(words, m: Model, index) -> dict[str, jnp.ndarray]:
    """One layer's weights, flat ``{path: array}``, in ``m.dtype``."""
    base = _base_key(words)
    shapes = layer_shapes(m)
    out = {}
    for leaf_id, (path, kind) in enumerate(LAYER_LEAVES):
        if path in shapes:
            key = jax.random.fold_in(jax.random.fold_in(base, leaf_id), index)
            out[path] = _draw(key, shapes[path], kind, m.dtype)
    return out


def embedding(words, m: Model) -> jnp.ndarray:
    """The tied table, N(0, (EMBED_STD / scale_emb)^2): rows enter the
    residual stream at EMBED_STD whatever the configuration's
    ``scale_emb``.  (Drawn at EMBED_STD, MiniCPM's x12 would make each
    token's own row outweigh the 80 residual branches, and a random
    model would only repeat its last token.)"""
    key = jax.random.fold_in(_base_key(words), EMBED_ID)
    z = jax.random.normal(key, (m.vocab_size, m.hidden_size), jnp.float32)
    return (EMBED_STD / m.scale_emb * z).astype(m.dtype)


def final_norm(words, m: Model) -> jnp.ndarray:
    key = jax.random.fold_in(_base_key(words), FINAL_NORM_ID)
    return _draw(key, (m.hidden_size,), "norm", m.dtype)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, a in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = a
    return out


def folds(m: Model) -> dict[str, float]:
    """Factors that carry the configuration's scalars into the weights of
    a block that has none.

    Norms see the residual stream scaled by ``c = sqrt(PROGRAM_RMS_EPS /
    eps)``: ``x / sqrt(mean(x^2) + eps) == c x / sqrt(mean((c x)^2) +
    c^2 eps)``, so the program's eps on ``c x`` is the configuration's
    eps on ``x``.  The embedding rows enter the stream times ``scale_emb
    * c``; each residual branch's output matrix adds its branch times
    ``scale_depth / sqrt(layers) * c``; and the final norm's scale,
    whose output meets the tied table (now times ``scale_emb * c``),
    takes ``dim_model_base / hidden_size / (scale_emb * c)``.  All are 1
    for a configuration without these scalars."""
    if m.qk_norm and m.rms_norm_eps != PROGRAM_RMS_EPS:
        raise ValueError("a qk-norm's eps cannot be folded into weights")
    c = math.sqrt(PROGRAM_RMS_EPS / m.rms_norm_eps)
    return {"embed": m.scale_emb * c, "branch_out": m.residual_scale * c,
            "final_norm": m.logit_scale / (m.scale_emb * c)}


def _scaled(a, factor: float):
    if factor == 1.0:
        return a
    return (a.astype(jnp.float32) * factor).astype(a.dtype)


def program_params(words, m: Model) -> dict:
    """All weights in the program's layout (layers stacked), with the
    configuration's scalars folded in (:func:`folds`)."""
    f = folds(m)
    stacked = jax.vmap(lambda i: layer(words, m, i))(
        jnp.arange(m.num_hidden_layers))
    for path in ("attn/wo", "mlp/wo"):
        stacked[path] = _scaled(stacked[path], f["branch_out"])
    return {"embed": {"table": _scaled(embedding(words, m), f["embed"])},
            "blocks": {"b0": _nest(stacked)},
            "final_norm": {"scale": _scaled(final_norm(words, m),
                                            f["final_norm"])}}


def make_on_device(seed: int, m: Model, device) -> dict:
    """:func:`program_params` as one jitted call, placed on ``device``."""
    fn = jax.jit(program_params, static_argnums=1,
                 out_shardings=jax.sharding.SingleDeviceSharding(device))
    return jax.block_until_ready(fn(seed_words(seed), m))


def check_layout(want_specs, m: Model) -> None:
    """Raise unless the program's parameter shapes are this module's."""
    got = jax.eval_shape(lambda w: program_params(w, m), seed_words(0))
    a, b = (jax.tree_util.tree_structure(t) for t in (want_specs, got))
    if a != b:
        raise ValueError(f"the program's parameter tree {a} is not the "
                         f"benchmark's {b}")
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want_specs),
                            jax.tree_util.tree_leaves(got), strict=True):
        if (w.shape, w.dtype) != (g.shape, g.dtype):
            raise ValueError(f"{jax.tree_util.keystr(path)}: the program "
                             f"wants {w.shape} {w.dtype}, the benchmark "
                             f"makes {g.shape} {g.dtype}")
