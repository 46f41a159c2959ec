"""deepseek-v2-lite-ep4 [moe] — one chip's share of DeepSeek-V2-Lite served
with expert parallelism over the four chips of a v5e 2x2 host.

DeepSeek-V2-Lite: 27L d_model=2048 16H, MLA (no q-LoRA, kv_lora_rank 512,
qk 128 nope + 64 rope, v 128) with YaRN rope (factor 40 over 4096 positions,
mscale 0.707), layer 0 dense (d_ff 10944), then 26 MoE layers: 64 routed
experts of width 1408, top-6 softmax gates kept as they are, and 2 shared
experts (one SwiGLU of width 2816); vocabulary 102400, untied head.

The chip holds 16 of each layer's 64 routed experts (offset 0), and a whole
copy of everything else; the router keeps its 64 outputs.
[hf:deepseek-ai/DeepSeek-V2-Lite]"""

from repro.models.common import ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-ep4",
    family="moe",
    n_layers=27,
    first_dense_layers=1,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,
    d_ff=10944,
    vocab_size=102400,
    attention="mla",
    kv_lora_rank=512,
    qk_rope_head_dim=64,
    qk_nope_head_dim=128,
    v_head_dim=128,
    rope_theta=10000.0,
    rope_yarn=YarnConfig(factor=40.0, original_max_position=4096,
                         beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                         mscale_all_dim=0.707),
    moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408,
                  dense_residual=True, dense_residual_ff=2816,
                  renormalize=False, dropless=True, n_held=16,
                  held_offset=0),
    tie_embeddings=False,
)
