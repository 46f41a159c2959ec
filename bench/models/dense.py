"""The dense decoder family: Llama-style blocks (RMSNorm before each
block, RoPE on rotated halves, grouped-query attention with an optional
qk-norm, a SwiGLU MLP) and a tied embedding table, with MiniCPM's
embedding, residual and logit scalars where a configuration states them.

A configuration names its family with ``"family"``; this file is
``bench/models/dense.py``, and everything here that knows a dense size
lives in it:

- :class:`Model`, the sizes under the published config's keys;
- :func:`program_pairs`, the program's ``ModelConfig`` fields that must
  match the file;
- :func:`program_params`, the weights in the program's layout;
- :func:`logits`, the float32 reference;
- :func:`prefill` and :func:`decode_step`, the operations and bytes a
  task cannot avoid (counted by the rules of ``bench/lib/flops.py``);
- :func:`reference_row_bytes` and :func:`reduced`.

The reference imports nothing of the program and takes nothing that the
program made: its weights are drawn again from the seed, one layer at a
time, so that it fits beside nothing else on the chip.  ``quantize``
gives the control: every weight matrix rounded to float8 (e4m3, one
scale per output channel), the step below the bfloat16 that the
configurations state.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from bench.lib import weights as W
from bench.lib.reference import F32, fp8_round, rms, rope

# keys of a configuration file that do not change what is computed
IGNORED = frozenset({"arch", "family", "source", "paper", "architectures",
                     "model_type", "reduced", "assumed",
                     "max_position_embeddings"})
# keys this family reads only to refuse any other value
FIXED = {"rope_scaling": None, "attention_bias": False,
         "use_sliding_window": False, "hidden_act": "silu"}


@dataclass(frozen=True)
class Model:
    """The sizes of a dense decoder, under the published config's keys."""

    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    vocab_size: int
    rms_norm_eps: float
    rope_theta: float
    qk_norm: bool = False
    scale_emb: float = 1.0
    scale_depth: float | None = None  # None: plain residual
    dim_model_base: int | None = None  # None: no logit scaling
    tie_word_embeddings: bool = True
    dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, cfg: dict) -> "Model":
        """The model of a configuration file.  Raises on a key this family
        neither reads nor ignores, and on a value it cannot compute."""
        names = set(cls.__dataclass_fields__)
        unknown = sorted(set(cfg) - names - IGNORED - set(FIXED)
                         - {"torch_dtype"})
        if unknown:
            raise ValueError(f"the dense family does not model {unknown}")
        bad = {k: cfg[k] for k, v in FIXED.items() if k in cfg and cfg[k] != v}
        if not cfg.get("tie_word_embeddings", True):
            bad["tie_word_embeddings"] = False
        if bad:
            raise ValueError(f"the dense family cannot compute {bad}")
        kw = {k: v for k, v in cfg.items() if k in names}
        if "head_dim" not in kw:
            kw["head_dim"] = cfg["hidden_size"] // cfg["num_attention_heads"]
        if "torch_dtype" in cfg:
            kw["dtype"] = cfg["torch_dtype"]
        return cls(**kw)

    @property
    def residual_scale(self) -> float:
        if self.scale_depth is None:
            return 1.0
        return self.scale_depth / math.sqrt(self.num_hidden_layers)

    @property
    def logit_scale(self) -> float:
        if self.dim_model_base is None:
            return 1.0
        return self.dim_model_base / self.hidden_size

    @property
    def kv_bytes_per_token(self) -> int:
        """Keys and values of one position, all layers, in ``dtype``."""
        return (2 * self.num_hidden_layers * self.num_key_value_heads
                * self.head_dim * self.bytes_per_value)

    @property
    def bytes_per_value(self) -> int:
        return {"bfloat16": 2, "float16": 2, "float32": 4}[self.dtype]


def program_pairs(m: Model) -> dict:
    """The program's ``ModelConfig`` fields and the values the
    configuration file gives them."""
    return {"d_model": m.hidden_size, "d_ff": m.intermediate_size,
            "n_layers": m.num_hidden_layers,
            "n_heads": m.num_attention_heads,
            "n_kv_heads": m.num_key_value_heads, "head_dim": m.head_dim,
            "vocab_size": m.vocab_size, "rope_theta": m.rope_theta,
            "qk_norm": m.qk_norm, "param_dtype": m.dtype,
            "compute_dtype": m.dtype,
            "tie_embeddings": m.tie_word_embeddings}


def reduced(cfg) -> Model:
    """The model at the widths of the program's ``ModelConfig`` ``cfg``
    (the CPU tests pass ``repro.configs.reduced(...)``), in float32, with
    none of MiniCPM's scalars."""
    return Model(
        hidden_size=cfg.d_model, intermediate_size=cfg.d_ff,
        num_hidden_layers=cfg.n_layers, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        vocab_size=cfg.vocab_size, rms_norm_eps=1e-6,
        rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm, dtype="float32")


# --- weights: the program's layout (``repro.models.lm``), stacked layers
# under ``blocks/b0``, weights as ``(in, out)`` matrices, a tied table

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
PROGRAM_RMS_EPS = 1e-6  # repro.models.layers.apply_norm


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


def layer(words, m: Model, index) -> dict[str, jnp.ndarray]:
    """One layer's weights, flat ``{path: array}``, in ``m.dtype``."""
    base = W.base_key(words)
    shapes = layer_shapes(m)
    out = {}
    for leaf_id, (path, kind) in enumerate(LAYER_LEAVES):
        if path in shapes:
            key = jax.random.fold_in(jax.random.fold_in(base, leaf_id), index)
            out[path] = W.draw(key, shapes[path], kind, m.dtype)
    return out


def embedding(words, m: Model) -> jnp.ndarray:
    """The tied table, N(0, (EMBED_STD / scale_emb)^2): rows enter the
    residual stream at EMBED_STD whatever the configuration's
    ``scale_emb``.  (Drawn at EMBED_STD, MiniCPM's x12 would make each
    token's own row outweigh the 80 residual branches, and a random
    model would only repeat its last token.)"""
    key = jax.random.fold_in(W.base_key(words), EMBED_ID)
    z = jax.random.normal(key, (m.vocab_size, m.hidden_size), jnp.float32)
    return (EMBED_STD / m.scale_emb * z).astype(m.dtype)


def final_norm(words, m: Model) -> jnp.ndarray:
    key = jax.random.fold_in(W.base_key(words), FINAL_NORM_ID)
    return W.draw(key, (m.hidden_size,), "norm", m.dtype)


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
            "blocks": {"b0": W.nest(stacked)},
            "final_norm": {"scale": _scaled(final_norm(words, m),
                                            f["final_norm"])}}


# --- the reference: the published description with the file's numbers


@partial(jax.jit, static_argnums=(2, 3))
def _block(x, w, m: Model, quantize: bool):
    w = {k: v.astype(F32) for k, v in w.items()}
    if quantize:
        w = {k: fp8_round(v, 0) if v.ndim == 2 else v for k, v in w.items()}
    R, T, _ = x.shape
    H, K, hd = m.num_attention_heads, m.num_key_value_heads, m.head_dim
    eps = m.rms_norm_eps
    h = rms(x, w["mixer_norm/scale"], eps)
    q = (h @ w["attn/wq"]).reshape(R, T, H, hd)
    k = (h @ w["attn/wk"]).reshape(R, T, K, hd)
    v = (h @ w["attn/wv"]).reshape(R, T, K, hd)
    if m.qk_norm:
        q = rms(q, w["attn/q_norm"], eps)
        k = rms(k, w["attn/k_norm"], eps)
    q, k = rope(q, m.rope_theta), rope(k, m.rope_theta)
    k = jnp.repeat(k, H // K, axis=2)  # query head i reads kv head i // (H/K)
    v = jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("rqhd,rkhd->rhqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("rhqk,rkhd->rqhd", p, v).reshape(R, T, H * hd)
    x = x + (o @ w["attn/wo"]) * m.residual_scale
    h = rms(x, w["mlp_norm/scale"], eps)
    mlp = (jax.nn.silu(h @ w["mlp/wg"]) * (h @ w["mlp/wi"])) @ w["mlp/wo"]
    return x + mlp * m.residual_scale


@partial(jax.jit, static_argnums=(2,))
def _embed(words, tokens, m: Model):
    return embedding(words, m)[tokens].astype(F32) * m.scale_emb


@partial(jax.jit, static_argnums=(3, 4))
def _head(words, x, rows_pos, m: Model, quantize: bool):
    table = embedding(words, m).astype(F32)
    if quantize:
        table = fp8_round(table, 1)
    x = rms(x, final_norm(words, m).astype(F32), m.rms_norm_eps)
    x = jnp.take(x, rows_pos, axis=1) * m.logit_scale
    return jnp.einsum("rnd,vd->rnv", x, table)


def logits(seed: int, m: Model, tokens, out_positions, *,
           quantize: bool = False):
    """Logits (rows, len(out_positions), vocab), float32, of ``tokens``
    (rows, T) at the positions ``out_positions``."""
    words = W.seed_words(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    lay = jax.jit(layer, static_argnums=1)
    with jax.default_matmul_precision("highest"):
        x = _embed(words, tokens, m)
        for i in range(m.num_hidden_layers):
            x = _block(x, lay(words, m, i), m, quantize)
        return _head(words, x, jnp.asarray(out_positions, jnp.int32), m,
                     quantize)


def reference_row_bytes(m: Model, T: int) -> int:
    """The reference's largest array per row of ``T`` tokens: one layer's
    float32 attention scores."""
    return 4 * m.num_attention_heads * T * T


# --- costs (the rules of ``bench/lib/flops.py``)


def layer_matmul_params(m: Model) -> int:
    d, hd = m.hidden_size, m.head_dim
    attn = d * hd * (2 * m.num_attention_heads + 2 * m.num_key_value_heads)
    return attn + 3 * d * m.intermediate_size


def weight_bytes(m: Model) -> int:
    """Matmul weights of all layers plus the embedding table."""
    params = (m.num_hidden_layers * layer_matmul_params(m)
              + m.vocab_size * m.hidden_size)
    return params * m.bytes_per_value


def _attention_flops(m: Model, pairs: int) -> int:
    """QK^T and PV over ``pairs`` (query, key) pairs, every head and layer."""
    return 4 * m.head_dim * m.num_attention_heads * m.num_hidden_layers * pairs


def prefill(m: Model, B: int, P: int) -> tuple[int, int]:
    """(FLOPs, bytes) of the prefill of B prompts of P tokens."""
    dense = 2 * B * P * m.num_hidden_layers * layer_matmul_params(m)
    attn = _attention_flops(m, B * P * (P + 1) // 2)
    head = 2 * B * m.hidden_size * m.vocab_size
    bytes_ = weight_bytes(m) + B * P * m.kv_bytes_per_token
    return dense + attn + head, bytes_


def decode_step(m: Model, B: int, filled: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one decode step whose new token sees ``filled``
    earlier positions."""
    dense = 2 * B * m.num_hidden_layers * layer_matmul_params(m)
    attn = _attention_flops(m, B * (filled + 1))
    head = 2 * B * m.hidden_size * m.vocab_size
    bytes_ = weight_bytes(m) + B * (filled + 1) * m.kv_bytes_per_token
    return dense + attn + head, bytes_
