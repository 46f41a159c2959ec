"""The DeepSeek-V2 family: multi-head latent attention (MLA) with YaRN
rope, leading dense layers, then mixture-of-experts layers with shared
experts, an untied head; one chip's share of the routed experts.

A configuration names its family with ``"family"``; this file is
``bench/models/deepseek_v2.py``.  It follows the published description
(DeepSeek-V2, arXiv:2405.04434, and the model's ``modeling_deepseek.py``):

- attention without q-LoRA: ``q = h Wq`` per head as 128 nope + 64 rope
  dims; ``[c, k_pe] = h Wkv_a``, ``c`` RMS-normed, ``[k_nope, v] = c
  Wkv_b``; the rope dims of queries and the one shared rope key rotate
  interleaved pairs (``x_2i``, ``x_2i+1``) at YaRN's frequencies; scores
  scale by ``q_head_dim ** -0.5 * mscale(factor, mscale_all_dim) ** 2``;
- the first ``first_k_dense_replace`` layers have a SwiGLU MLP of
  ``intermediate_size``; the others route: float32 softmax over the
  router's experts, the top ``num_experts_per_tok`` kept as they are
  (``norm_topk_prob`` false; ``routed_scaling_factor`` 1), plus
  ``n_shared_experts`` shared experts as one SwiGLU;
- the chip holds ``n_routed_experts`` of the router's ``router_experts``
  experts, from ``held_experts_offset``: a token's choices among the
  others are left out here, in the program and in this reference alike
  (expert parallelism; the other chips add them).

The reference imports nothing of the program and takes nothing that the
program made: its weights are drawn again from the seed, one layer at a
time.  ``quantize`` gives the control: every weight matrix rounded to
float8 (e4m3, one scale per output channel).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from bench.lib import weights as W
from bench.lib.reference import F32, fp8_round, rms

# keys of a configuration file that do not change what is computed
IGNORED = frozenset({"arch", "family", "source", "paper", "architectures",
                     "model_type", "reduced", "assumed", "torch_dtype",
                     "max_position_embeddings", "seq_aux"})
# keys this family reads only to refuse any other value
FIXED = {"q_lora_rank": None, "scoring_func": "softmax",
         "topk_method": "greedy", "n_group": 1, "topk_group": 1,
         "moe_layer_freq": 1, "attention_bias": False, "hidden_act": "silu",
         "tie_word_embeddings": False, "routed_scaling_factor": 1}
YARN_KEYS = ("type", "factor", "original_max_position_embeddings",
             "beta_fast", "beta_slow", "mscale", "mscale_all_dim")


@dataclass(frozen=True)
class Yarn:
    factor: float
    original_max_position_embeddings: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float


@dataclass(frozen=True)
class Model:
    """The sizes of a DeepSeek-V2 model, under the published config's
    keys; ``n_routed_experts`` is the share held here."""

    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    num_attention_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int
    router_experts: int
    held_experts_offset: int
    num_experts_per_tok: int
    n_shared_experts: int
    norm_topk_prob: bool
    vocab_size: int
    rms_norm_eps: float
    rope_theta: float
    rope_scaling: Yarn
    dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, cfg: dict) -> "Model":
        """The model of a configuration file.  Raises on a key this family
        neither reads nor ignores, and on a value it cannot compute."""
        names = set(cls.__dataclass_fields__)
        unknown = sorted(set(cfg) - names - IGNORED - set(FIXED)
                         - {"num_key_value_heads"})
        if unknown:
            raise ValueError(f"the deepseek_v2 family does not model "
                             f"{unknown}")
        bad = {k: cfg[k] for k, v in FIXED.items() if k in cfg and cfg[k] != v}
        if cfg.get("num_key_value_heads", cfg["num_attention_heads"]) != (
                cfg["num_attention_heads"]):
            bad["num_key_value_heads"] = cfg["num_key_value_heads"]
        rs = cfg.get("rope_scaling")
        if not isinstance(rs, dict) or rs.get("type") != "yarn" or (
                set(rs) - set(YARN_KEYS)):
            bad["rope_scaling"] = rs
        if cfg.get("held_experts_offset", 0) + cfg["n_routed_experts"] > (
                cfg.get("router_experts", cfg["n_routed_experts"])):
            bad["n_routed_experts"] = cfg["n_routed_experts"]
        if bad:
            raise ValueError(f"the deepseek_v2 family cannot compute {bad}")
        kw = {k: v for k, v in cfg.items() if k in names}
        kw.setdefault("router_experts", cfg["n_routed_experts"])
        kw.setdefault("held_experts_offset", 0)
        kw["rope_scaling"] = Yarn(**{k: rs[k] for k in YARN_KEYS[1:]})
        if "torch_dtype" in cfg:
            kw["dtype"] = cfg["torch_dtype"]
        return cls(**kw)

    @property
    def bytes_per_value(self) -> int:
        return {"bfloat16": 2, "float16": 2, "float32": 4}[self.dtype]

    @property
    def moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def shared_width(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def kv_bytes_per_token(self) -> int:
        """The latent and the rope key of one position, all layers."""
        return (self.num_hidden_layers
                * (self.kv_lora_rank + self.qk_rope_head_dim)
                * self.bytes_per_value)

    @property
    def held_share(self) -> float:
        """The expected share of a token's choices that fall here."""
        return self.n_routed_experts / self.router_experts


class Fields(dict):
    """Equal to an object whose attributes hold these values (a nested
    dataclass of the program's config, held against the file)."""

    __hash__ = None

    def __eq__(self, other):
        return all(getattr(other, k, None) == v for k, v in self.items())

    def __ne__(self, other):
        return not self.__eq__(other)


def program_pairs(m: Model) -> dict:
    """The program's ``ModelConfig`` fields and the values the
    configuration file gives them."""
    y = m.rope_scaling
    return {"d_model": m.hidden_size, "d_ff": m.intermediate_size,
            "n_layers": m.num_hidden_layers,
            "first_dense_layers": m.first_k_dense_replace,
            "n_heads": m.num_attention_heads, "attention": "mla",
            "q_lora_rank": 0, "kv_lora_rank": m.kv_lora_rank,
            "qk_nope_head_dim": m.qk_nope_head_dim,
            "qk_rope_head_dim": m.qk_rope_head_dim,
            "v_head_dim": m.v_head_dim, "vocab_size": m.vocab_size,
            "rope_theta": m.rope_theta, "tie_embeddings": False,
            "param_dtype": m.dtype, "compute_dtype": m.dtype,
            "rope_yarn": Fields(
                factor=y.factor,
                original_max_position=y.original_max_position_embeddings,
                beta_fast=y.beta_fast, beta_slow=y.beta_slow,
                mscale=y.mscale, mscale_all_dim=y.mscale_all_dim),
            "moe": Fields(
                n_experts=m.router_experts, top_k=m.num_experts_per_tok,
                d_ff_expert=m.moe_intermediate_size, dropless=True,
                n_held=m.n_routed_experts,
                held_offset=m.held_experts_offset,
                renormalize=m.norm_topk_prob,
                dense_residual=m.n_shared_experts > 0,
                dense_residual_ff=m.shared_width)}


def reduced(cfg) -> Model:
    """The model at the widths of the program's ``ModelConfig`` ``cfg``
    (the CPU tests pass ``repro.configs.reduced(...)``), in float32."""
    e, y = cfg.moe, cfg.rope_yarn
    return Model(
        hidden_size=cfg.d_model, intermediate_size=cfg.d_ff,
        moe_intermediate_size=e.d_ff_expert,
        num_hidden_layers=cfg.n_layers,
        first_k_dense_replace=cfg.first_dense_layers,
        num_attention_heads=cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        n_routed_experts=e.held, router_experts=e.n_experts,
        held_experts_offset=e.held_offset, num_experts_per_tok=e.top_k,
        n_shared_experts=e.dense_residual_ff // e.d_ff_expert,
        norm_topk_prob=e.renormalize,
        vocab_size=cfg.vocab_size, rms_norm_eps=1e-6,
        rope_theta=cfg.rope_theta,
        rope_scaling=Yarn(y.factor, y.original_max_position, y.beta_fast,
                          y.beta_slow, y.mscale, y.mscale_all_dim),
        dtype="float32")


# --- weights: drawn in the published layout (rope dims as interleaved
# pairs), handed to the program in its own (rope dims as halves)

# leaf id -> (path, kind); the ids are part of the weights' definition
LAYER_LEAVES = (
    ("mixer_norm/scale", "norm"),
    ("attn/wq", "matrix"),
    ("attn/wkv_a", "matrix"),
    ("attn/kv_a_norm", "norm"),
    ("attn/wkv_b", "matrix"),
    ("attn/wo", "matrix"),
    ("mlp_norm/scale", "norm"),
    ("mlp/wi", "matrix"),
    ("mlp/wg", "matrix"),
    ("mlp/wo", "matrix"),
    ("moe/router", "matrix"),
    ("moe/experts/wi", "expert"),
    ("moe/experts/wg", "expert"),
    ("moe/experts/wo", "expert"),
    ("moe/residual/wi", "matrix"),
    ("moe/residual/wg", "matrix"),
    ("moe/residual/wo", "matrix"),
)
EMBED_ID, FINAL_NORM_ID, HEAD_ID = 100, 101, 102
EMBED_STD = 0.02
ROUTER_DTYPE = "float32"  # the program keeps the router in float32


def layer_shapes(m: Model, moe: bool) -> dict[str, tuple]:
    """One layer's leaves; an expert leaf's shape is one expert's."""
    d, H = m.hidden_size, m.num_attention_heads
    R, rope = m.kv_lora_rank, m.qk_rope_head_dim
    shapes = {
        "mixer_norm/scale": (d,),
        "attn/wq": (d, H * m.q_head_dim),
        "attn/wkv_a": (d, R + rope),
        "attn/kv_a_norm": (R,),
        "attn/wkv_b": (R, H * (m.qk_nope_head_dim + m.v_head_dim)),
        "attn/wo": (H * m.v_head_dim, d),
        "mlp_norm/scale": (d,),
    }
    if not moe:
        ff = m.intermediate_size
        return shapes | {"mlp/wi": (d, ff), "mlp/wg": (d, ff),
                         "mlp/wo": (ff, d)}
    fe, fs = m.moe_intermediate_size, m.shared_width
    return shapes | {"moe/router": (d, m.router_experts),
                     "moe/experts/wi": (d, fe), "moe/experts/wg": (d, fe),
                     "moe/experts/wo": (fe, d),
                     "moe/residual/wi": (d, fs), "moe/residual/wg": (d, fs),
                     "moe/residual/wo": (fs, d)}


def layer(words, m: Model, index, moe: bool) -> dict[str, jnp.ndarray]:
    """Layer ``index``'s weights, flat ``{path: array}``, published layout;
    an expert leaf stacks the held experts, each drawn from its own key
    (its index among the router's experts)."""
    base = W.base_key(words)
    shapes = layer_shapes(m, moe)
    held = m.held_experts_offset + jnp.arange(m.n_routed_experts)
    out = {}
    for leaf_id, (path, kind) in enumerate(LAYER_LEAVES):
        if path not in shapes:
            continue
        key = jax.random.fold_in(jax.random.fold_in(base, leaf_id), index)
        if kind == "expert":
            out[path] = jax.vmap(lambda e, k=key, s=shapes[path]: W.draw(
                jax.random.fold_in(k, e), s, "matrix", m.dtype))(held)
        else:
            dtype = ROUTER_DTYPE if path == "moe/router" else m.dtype
            out[path] = W.draw(key, shapes[path], kind, dtype)
    return out


def embedding(words, m: Model) -> jnp.ndarray:
    key = jax.random.fold_in(W.base_key(words), EMBED_ID)
    z = jax.random.normal(key, (m.vocab_size, m.hidden_size), jnp.float32)
    return (EMBED_STD * z).astype(m.dtype)


def head(words, m: Model) -> jnp.ndarray:
    """The untied output head, (vocab, hidden), N(0, 1/hidden)."""
    key = jax.random.fold_in(W.base_key(words), HEAD_ID)
    z = jax.random.normal(key, (m.vocab_size, m.hidden_size), jnp.float32)
    return (z * m.hidden_size ** -0.5).astype(m.dtype)


def final_norm(words, m: Model) -> jnp.ndarray:
    key = jax.random.fold_in(W.base_key(words), FINAL_NORM_ID)
    return W.draw(key, (m.hidden_size,), "norm", m.dtype)


def halves_from_pairs(n: int) -> jnp.ndarray:
    """Columns that turn interleaved pairs into rotated halves: the
    published ``view(d // 2, 2).transpose`` of the rope dims."""
    return jnp.concatenate([jnp.arange(0, n, 2), jnp.arange(1, n, 2)])


def to_program(w: dict, m: Model) -> dict:
    """One layer's weights in the program's layout: the rope columns of
    ``wq`` (per head) and ``wkv_a`` reordered so that the program's
    rotated halves compute the published interleaved rotation."""
    d, H, nope = m.hidden_size, m.num_attention_heads, m.qk_nope_head_dim
    perm = halves_from_pairs(m.qk_rope_head_dim)
    wq = w["attn/wq"].reshape(d, H, m.q_head_dim)
    wq = jnp.concatenate([wq[..., :nope], wq[..., nope:][..., perm]], -1)
    kv = w["attn/wkv_a"]
    R = m.kv_lora_rank
    return w | {"attn/wq": wq.reshape(d, H * m.q_head_dim),
                "attn/wkv_a": jnp.concatenate([kv[:, :R], kv[:, R:][:, perm]],
                                              -1)}


def program_params(words, m: Model) -> dict:
    """All weights in the program's layout: the leading dense layers
    stacked under ``blocks/lead``, the expert layers under ``blocks/b0``
    (drawn one layer at a time, so that no layer's float32 draw of the
    whole stack is ever held)."""
    def stack(first, n, moe):
        return jax.lax.map(lambda i: to_program(layer(words, m, i, moe), m),
                           first + jnp.arange(n))

    k = m.first_k_dense_replace
    return {"embed": {"table": embedding(words, m)},
            "blocks": {"lead": W.nest(stack(0, k, False)),
                       "b0": W.nest(stack(k, m.moe_layers, True))},
            "final_norm": {"scale": final_norm(words, m)},
            "lm_head": {"table": head(words, m)}}


# --- the reference: the published description with the file's numbers


def yarn_get_mscale(scale: float, mscale: float) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_correction_range(m: Model) -> tuple[int, int]:
    """``yarn_find_correction_range(beta_fast, beta_slow, dim, base,
    original_max_position_embeddings)``: pairs [low, high)."""
    y, dim, base = m.rope_scaling, m.qk_rope_head_dim, m.rope_theta

    def correction_dim(rotations):
        return (dim * math.log(y.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    return (max(math.floor(correction_dim(y.beta_fast)), 0),
            min(math.ceil(correction_dim(y.beta_slow)), dim - 1))


def yarn_inv_freq(m: Model) -> jnp.ndarray:
    y, dim, base = m.rope_scaling, m.qk_rope_head_dim, m.rope_theta
    freq_extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    freq_inter = freq_extra / y.factor
    low, high = yarn_correction_range(m)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0, 1)
    inv_freq_mask = 1.0 - ramp
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def softmax_scale(m: Model) -> float:
    y = m.rope_scaling
    scale = m.q_head_dim ** -0.5
    if y.mscale_all_dim:
        scale *= yarn_get_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def yarn_rope(x, m: Model):
    """x: (R, T, heads, rope) as interleaved pairs, positions 0..T-1;
    returns the rotated dims as halves (``apply_rotary_pos_emb``)."""
    y = m.rope_scaling
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * yarn_inv_freq(m)
    amplitude = (yarn_get_mscale(y.factor, y.mscale)
                 / yarn_get_mscale(y.factor, y.mscale_all_dim))
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None] * amplitude
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None] * amplitude
    R, T, h, n = x.shape
    x = x.reshape(R, T, h, n // 2, 2).swapaxes(-1, -2).reshape(R, T, h, n)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _quantized(w: dict, quantize: bool) -> dict:
    """Float32 weights; with ``quantize`` every matrix through float8
    along its reduction axis (the second-to-last)."""
    w = {k: v.astype(F32) for k, v in w.items()}
    if quantize:
        w = {k: fp8_round(v, v.ndim - 2) if v.ndim >= 2 else v
             for k, v in w.items()}
    return w


def _attention(x, w, m: Model):
    R, T, _ = x.shape
    H, nope, rope = m.num_attention_heads, m.qk_nope_head_dim, m.qk_rope_head_dim
    vd, r = m.v_head_dim, m.kv_lora_rank
    h = rms(x, w["mixer_norm/scale"], m.rms_norm_eps)
    q = (h @ w["attn/wq"]).reshape(R, T, H, nope + rope)
    kv_a = h @ w["attn/wkv_a"]
    c = rms(kv_a[..., :r], w["attn/kv_a_norm"], m.rms_norm_eps)
    k_pe = yarn_rope(kv_a[..., r:][:, :, None, :], m)
    kv = (c @ w["attn/wkv_b"]).reshape(R, T, H, nope + vd)
    q = jnp.concatenate([q[..., :nope], yarn_rope(q[..., nope:], m)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (R, T, H, rope))], -1)
    s = jnp.einsum("rqhd,rkhd->rhqk", q, k) * softmax_scale(m)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("rhqk,rkhd->rqhd", p, kv[..., nope:]).reshape(R, T, H * vd)
    return x + o @ w["attn/wo"]


def _swiglu(h, wg, wi, wo):
    return (jax.nn.silu(h @ wg) * (h @ wi)) @ wo


def gates(h, router, m: Model):
    """(top-k gates, expert ids) of every token: ``MoEGate.forward``."""
    scores = jax.nn.softmax(h @ router, axis=-1)
    weight, idx = jax.lax.top_k(scores, m.num_experts_per_tok)
    if m.num_experts_per_tok > 1 and m.norm_topk_prob:
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
    return weight, idx


@partial(jax.jit, static_argnums=(2, 3))
def _dense_block(x, w, m: Model, quantize: bool):
    w = _quantized(w, quantize)
    x = _attention(x, w, m)
    h = rms(x, w["mlp_norm/scale"], m.rms_norm_eps)
    return x + _swiglu(h, w["mlp/wg"], w["mlp/wi"], w["mlp/wo"])


@partial(jax.jit, static_argnums=(2, 3))
def _moe_block(x, w, m: Model, quantize: bool):
    w = _quantized(w, quantize)
    x = _attention(x, w, m)
    h = rms(x, w["mlp_norm/scale"], m.rms_norm_eps)
    weight, idx = gates(h, w["moe/router"], m)
    # each held expert's gate for every token: 0 where not chosen
    held = m.held_experts_offset + jnp.arange(m.n_routed_experts)
    g = jnp.sum(jnp.where(idx[..., None] == held, weight[..., None], 0.0), -2)
    a = jnp.einsum("rtd,edf->rtef", h, w["moe/experts/wg"])
    b = jnp.einsum("rtd,edf->rtef", h, w["moe/experts/wi"])
    routed = jnp.einsum("rtef,efd->rtd", jax.nn.silu(a) * b * g[..., None],
                        w["moe/experts/wo"])
    shared = _swiglu(h, w["moe/residual/wg"], w["moe/residual/wi"],
                     w["moe/residual/wo"])
    return x + routed + shared


@partial(jax.jit, static_argnums=(2,))
def _embed(words, tokens, m: Model):
    return embedding(words, m)[tokens].astype(F32)


@partial(jax.jit, static_argnums=(3, 4))
def _head(words, x, rows_pos, m: Model, quantize: bool):
    table = head(words, m).astype(F32)
    if quantize:
        table = fp8_round(table, 1)
    x = rms(x, final_norm(words, m).astype(F32), m.rms_norm_eps)
    x = jnp.take(x, rows_pos, axis=1)
    return jnp.einsum("rnd,vd->rnv", x, table)


def logits(seed: int, m: Model, tokens, out_positions, *,
           quantize: bool = False):
    """Logits (rows, len(out_positions), vocab), float32, of ``tokens``
    (rows, T) at the positions ``out_positions``."""
    words = W.seed_words(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    lay = jax.jit(layer, static_argnums=(1, 3))
    with jax.default_matmul_precision("highest"):
        x = _embed(words, tokens, m)
        for i in range(m.num_hidden_layers):
            if i < m.first_k_dense_replace:
                x = _dense_block(x, lay(words, m, i, False), m, quantize)
            else:
                x = _moe_block(x, lay(words, m, i, True), m, quantize)
        return _head(words, x, jnp.asarray(out_positions, jnp.int32), m,
                     quantize)


def reference_row_bytes(m: Model, T: int) -> int:
    """The reference's largest arrays per row of ``T`` tokens: one
    layer's float32 attention scores, or its three (T, held experts,
    width) expert activations."""
    return max(4 * m.num_attention_heads * T * T,
               3 * 4 * T * m.n_routed_experts * m.moe_intermediate_size)


# --- costs (the rules of ``bench/lib/flops.py``)


def attention_params(m: Model) -> int:
    """Wq, Wkv_a, Wkv_b and Wo of one layer.  Decode's absorbed form
    multiplies by Wkv_b's two halves once per token, as prefill does."""
    d, H = m.hidden_size, m.num_attention_heads
    return (d * H * m.q_head_dim
            + d * (m.kv_lora_rank + m.qk_rope_head_dim)
            + m.kv_lora_rank * H * (m.qk_nope_head_dim + m.v_head_dim)
            + H * m.v_head_dim * d)


def expert_params(m: Model) -> int:
    """One routed expert's SwiGLU."""
    return 3 * m.hidden_size * m.moe_intermediate_size


def weight_bytes(m: Model) -> int:
    """Every layer's matmul weights (all held experts; the router in
    float32) plus the head."""
    b = m.bytes_per_value
    d = m.hidden_size
    per_moe = (m.n_routed_experts * expert_params(m)
               + 3 * d * m.shared_width) * b + d * m.router_experts * 4
    return (m.num_hidden_layers * attention_params(m) * b
            + m.first_k_dense_replace * 3 * d * m.intermediate_size * b
            + m.moe_layers * per_moe + m.vocab_size * d * b)


def token_matmul_flops(m: Model) -> float:
    """Matmul FLOPs of one token through every layer, the routed experts
    at the expected share of its choices held here."""
    d = m.hidden_size
    moe = (d * m.router_experts
           + m.num_experts_per_tok * m.held_share * expert_params(m)
           + 3 * d * m.shared_width)
    return 2 * (m.num_hidden_layers * attention_params(m)
                + m.first_k_dense_replace * 3 * d * m.intermediate_size
                + m.moe_layers * moe)


def prefill(m: Model, B: int, P: int) -> tuple[float, int]:
    """(FLOPs, bytes) of the prefill of B prompts of P tokens.  Attention
    is materialised: a (query, key) pair costs QK over the query head and
    PV over the value head."""
    H, L = m.num_attention_heads, m.num_hidden_layers
    pairs = B * P * (P + 1) // 2
    attn = 2 * H * (m.q_head_dim + m.v_head_dim) * L * pairs
    head_ = 2 * B * m.hidden_size * m.vocab_size
    flops = B * P * token_matmul_flops(m) + attn + head_
    return flops, weight_bytes(m) + B * P * m.kv_bytes_per_token


def decode_step(m: Model, B: int, filled: int) -> tuple[float, int]:
    """(FLOPs, bytes) of one decode step whose new token sees ``filled``
    earlier positions.  Attention is absorbed: a pair costs the scores
    over the latent and rope key and the weighted sum over the latent."""
    H, L, r = m.num_attention_heads, m.num_hidden_layers, m.kv_lora_rank
    pairs = B * (filled + 1)
    attn = 2 * H * (2 * r + m.qk_rope_head_dim) * L * pairs
    head_ = 2 * B * m.hidden_size * m.vocab_size
    flops = B * token_matmul_flops(m) + attn + head_
    return flops, weight_bytes(m) + pairs * m.kv_bytes_per_token


def expert_phase(m: Model, tokens: int) -> tuple[float, int]:
    """(FLOPs, bytes) of the routed experts' grouped matmul over
    ``tokens`` tokens: every held expert read once, the choices at their
    expected held share."""
    flops = (2 * tokens * m.num_experts_per_tok * m.held_share
             * expert_params(m) * m.moe_layers)
    return flops, m.moe_layers * m.n_routed_experts * expert_params(m) * (
        m.bytes_per_value)


def task_extras(m: Model, B: int, P: int, N: int, peak_flops: float,
                peak_bytes_per_s: float) -> dict:
    """The expert phase's FLOPs, bytes and least time in prefill and in
    one decode step, for ``expert_roofline``."""
    out = {}
    for phase, tokens in (("prefill", B * P), ("decode_step", B)):
        f, b = expert_phase(m, tokens)
        out[f"expert_{phase}_flops"] = f
        out[f"expert_{phase}_bytes"] = b
        out[f"expert_{phase}_least_s"] = max(f / peak_flops,
                                             b / peak_bytes_per_s)
    return out
