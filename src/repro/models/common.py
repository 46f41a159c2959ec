"""Model configuration for every assigned architecture.

One ``ModelConfig`` dataclass covers the whole assigned pool: dense GQA
transformers (llama3.2, qwen3, minicpm, phi-3-vision backbone), MLA
(minicpm3, deepseek-v2 with YaRN), token-dropping MoE with optional dense
residual (llama4-maverick, arctic, jamba), a dropless MoE layer over the
experts one chip holds (deepseek-v2), Mamba-1 SSM (falcon-mamba), the jamba
hybrid interleave, and the whisper encoder-decoder backbone.

A model is described as a *block pattern* (a short tuple of ``BlockSpec``)
repeated ``n_repeats`` times.  The forward pass lax.scan's over the repeats,
so HLO size stays O(pattern) instead of O(layers) and 48-72 layer configs
lower quickly.  ``first_dense_layers`` leading (attention, dense MLP) layers
run before the scanned repeats (DeepSeek's ``first_k_dense_replace``).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Any

import jax.numpy as jnp

Pytree = Any


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts: token-dropping (capacity-factor) by default, or
    ``dropless`` over the ``n_held`` experts from ``held_offset`` that this
    chip holds of the ``n_experts`` the router scores (expert parallelism;
    the other shares live on other chips)."""

    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    # Routing-group size (tokens per capacity group).  The dispatch/combine
    # one-hots are O(tokens x E x capacity) = O(tokens^2 * cf * k / groups),
    # so small groups are essential at scale: G=256 keeps the dispatch
    # tensor ~13x smaller than per-sequence grouping for a 128-expert MoE.
    # 0 = one group per sequence (the naive formulation).
    group_size: int = 256
    # Arctic-style: a dense FFN residual branch computed for every token in
    # parallel with the routed experts.
    dense_residual: bool = False
    dense_residual_ff: int = 0
    router_z_loss: float = 1e-3
    load_balance_loss: float = 1e-2
    # routed expert width; 0 -> the model's d_ff
    d_ff_expert: int = 0
    # renormalise the top-k gates to sum to 1 (else keep them as they are)
    renormalize: bool = True
    dropless: bool = False
    n_held: int = 0  # 0 -> all n_experts
    held_offset: int = 0

    def __post_init__(self) -> None:
        if self.n_held and not self.dropless:
            raise ValueError("only the dropless layer holds a share of the "
                             "experts")

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rope scaling (DeepSeek-V2's ``rope_scaling`` of type yarn)."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-1 selective state space block."""

    state_dim: int = 16
    conv_width: int = 4
    expand: int = 2
    dt_rank: int = 0  # 0 -> ceil(d_model / 16)

    def resolved_dt_rank(self, d_model: int) -> int:
        return self.dt_rank if self.dt_rank > 0 else -(-d_model // 16)


@dataclass(frozen=True)
class BlockSpec:
    """One layer's shape: a mixer plus an MLP."""

    mixer: str = "attn"  # "attn" | "mamba" | "none"
    mlp: str = "dense"  # "dense" | "moe" | "none"
    # sliding window for this block's attention (None = full/causal).
    window: int | None = None


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // n_heads

    # block pattern; if empty, a homogeneous ("attn","dense"/"moe") stack is
    # derived in __post_init__ replacement helpers below.
    pattern: tuple[BlockSpec, ...] = ()

    # attention details
    attention: str = "gqa"  # "gqa" | "mla" | "none"
    qk_norm: bool = False
    rope_theta: float = 10000.0
    rope_yarn: YarnConfig | None = None
    # MLA (DeepSeek/MiniCPM3 style multi-head latent attention)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    qk_nope_head_dim: int = 0
    v_head_dim: int = 0

    moe: MoEConfig | None = None
    ssm: SSMConfig | None = None
    # leading (attention, dense MLP at d_ff) layers before the pattern's
    # repeats; counted in n_layers
    first_dense_layers: int = 0

    # encoder-decoder (whisper backbone)
    is_encoder_decoder: bool = False
    n_encoder_layers: int = 0
    encoder_seq_len: int = 1500  # whisper stub frontend frames

    # modality frontend stubs
    frontend: str | None = None  # None | "audio" | "vision"
    n_patch_tokens: int = 256  # vision stub: patch embeds replacing prefix

    norm_type: str = "rmsnorm"  # "rmsnorm" | "layernorm"
    mlp_act: str = "swiglu"  # "swiglu" | "gelu"
    tie_embeddings: bool = False

    # numerics / training policy
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    remat: bool = False
    opt_state_dtype: str = "float32"  # float32 | bfloat16 | int8
    # sliding window applied to *attention* blocks only at long context
    long_context_window: int | None = None

    max_seq_len: int = 4096

    def __post_init__(self) -> None:
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if not self.pattern:
            mlp = "moe" if self.moe is not None else "dense"
            mixer = "mamba" if self.family == "ssm" else "attn"
            object.__setattr__(self, "pattern", (BlockSpec(mixer=mixer, mlp=mlp),))
        if (self.n_layers - self.first_dense_layers) % len(self.pattern) != 0:
            raise ValueError(
                f"{self.name}: n_layers={self.n_layers} less "
                f"{self.first_dense_layers} leading dense layers is not "
                f"divisible by pattern length {len(self.pattern)}"
            )

    # ------------------------------------------------------------------ #
    @property
    def n_repeats(self) -> int:
        return (self.n_layers - self.first_dense_layers) // len(self.pattern)

    @property
    def d_inner(self) -> int:
        assert self.ssm is not None
        return self.ssm.expand * self.d_model

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def uses_attention(self) -> bool:
        return any(b.mixer == "attn" for b in self.pattern)

    @property
    def uses_mamba(self) -> bool:
        return any(b.mixer == "mamba" for b in self.pattern)

    @property
    def subquadratic(self) -> bool:
        """True if the arch can run 500k-token decode (SSM/hybrid-window)."""
        return self.family in ("ssm", "hybrid")

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ---------------- parameter counting (for 6ND roofline) ------------ #
    def _attn_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        if self.attention == "mla":
            qr = self.q_lora_rank or self.d_model
            p = 0
            if self.q_lora_rank:
                p += d * self.q_lora_rank
            p += qr * self.n_heads * (self.qk_nope_head_dim + self.qk_rope_head_dim)
            p += d * (self.kv_lora_rank + self.qk_rope_head_dim)
            p += self.kv_lora_rank * self.n_heads * (self.qk_nope_head_dim + self.v_head_dim)
            p += self.n_heads * self.v_head_dim * d
            return p
        q = d * self.n_heads * hd
        kv = 2 * d * self.n_kv_heads * hd
        o = self.n_heads * hd * d
        return q + kv + o

    def _dense_mlp_params(self, d_ff: int | None = None) -> int:
        ff = d_ff or self.d_ff
        mult = 3 if self.mlp_act == "swiglu" else 2
        return mult * self.d_model * ff

    def _moe_params(self) -> tuple[int, int]:
        """(total, active) params of one MoE layer: the experts held here."""
        assert self.moe is not None
        e = self._dense_mlp_params(self.moe.d_ff_expert or None)
        total = self.moe.held * e + self.d_model * self.moe.n_experts
        active = self.moe.top_k * e
        if self.moe.dense_residual:
            r = self._dense_mlp_params(self.moe.dense_residual_ff or self.d_ff)
            total += r
            active += r
        return total, active

    def _mamba_params(self) -> int:
        assert self.ssm is not None
        di, d = self.d_inner, self.d_model
        s = self.ssm
        dtr = s.resolved_dt_rank(d)
        return (
            d * 2 * di  # in_proj (x and gate)
            + di * s.conv_width
            + di * (dtr + 2 * s.state_dim)  # x_proj
            + dtr * di  # dt_proj
            + di * s.state_dim  # A_log
            + di  # D
            + di * d  # out_proj
        )

    def param_counts(self) -> tuple[int, int]:
        """Returns (total_params, active_params) excluding embeddings'
        contribution to FLOPs is handled separately; embeddings included in
        totals."""
        total = active = 0
        for b in self.pattern:
            if b.mixer == "attn":
                p = self._attn_params()
                total += p
                active += p
            elif b.mixer == "mamba":
                p = self._mamba_params()
                total += p
                active += p
            if b.mlp == "dense":
                p = self._dense_mlp_params()
                total += p
                active += p
            elif b.mlp == "moe":
                t, a = self._moe_params()
                total += t
                active += a
        total *= self.n_repeats
        active *= self.n_repeats
        lead = self.first_dense_layers * (self._attn_params()
                                          + self._dense_mlp_params())
        total += lead
        active += lead
        emb = self.vocab_size * self.d_model
        emb_total = emb if self.tie_embeddings else 2 * emb
        if self.is_encoder_decoder:
            enc_per_layer = self._attn_params() + self._dense_mlp_params()
            # decoder cross-attention
            dec_cross = self._attn_params() * self.n_layers
            total += enc_per_layer * self.n_encoder_layers + dec_cross
            active += enc_per_layer * self.n_encoder_layers + dec_cross
        total += emb_total
        active += emb_total
        return total, active


def default_block_pattern(
    *, moe_period: int = 1, attn_period: int = 1, n: int = 1
) -> tuple[BlockSpec, ...]:
    """Build an interleaved pattern.

    ``attn_period=8`` -> 1 attention block followed by 7 mamba blocks
    (jamba's 1:7).  ``moe_period=2`` -> alternate dense / moe MLPs.
    """
    blocks = []
    for i in range(n):
        mixer = "attn" if i % attn_period == 0 else "mamba"
        mlp = "moe" if i % moe_period == (moe_period - 1) else "dense"
        blocks.append(BlockSpec(mixer=mixer, mlp=mlp))
    return tuple(blocks)


def sinusoidal_positions(n: int, d: int, dtype=jnp.float32) -> jnp.ndarray:
    """Whisper-style sinusoidal position embeddings."""
    half = d // 2
    log_timescale = math.log(10000.0) / max(half - 1, 1)
    inv = jnp.exp(-log_timescale * jnp.arange(half, dtype=jnp.float32))
    scaled = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=-1).astype(dtype)
