"""Operations and bytes that one generation task cannot avoid, from shapes.

A task is ``B`` prompts of ``P`` tokens and ``N`` greedy new tokens:
prefill over the prompts yields token 0, then ``N - 1`` decode steps
yield tokens 1..N-1.  Counted, and nothing else:

- every matmul weight, and the tied embedding table as the LM head, is
  read once per prefill and once per decode step;
- keys and values are written once per position, and a decode step reads
  them for the positions already filled, never for the cache's unused
  tail;
- attention is causal: a query at position i meets i + 1 keys;
- the LM head runs only where its logits are used: the last prompt
  position and the positions that choose tokens 1..N-1.

Norm scales, the embedding gather and activations are left out: they
are far below a percent of either count at these sizes.
"""

from __future__ import annotations

from .spec import Model


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


def task(m: Model, B: int, P: int, N: int, peak_flops: float,
         peak_bytes_per_s: float) -> dict:
    """FLOPs, bytes and the least time of one task on a chip with the
    given peaks: each phase takes the larger of its FLOPs over the peak
    rate and its bytes over the memory bandwidth."""
    def least(fb):
        return max(fb[0] / peak_flops, fb[1] / peak_bytes_per_s)

    pre = prefill(m, B, P)
    steps = [decode_step(m, B, P + j - 1) for j in range(1, N)]
    return {
        "flops": pre[0] + sum(f for f, _ in steps),
        "bytes": pre[1] + sum(b for _, b in steps),
        "prefill_least_s": least(pre),
        "decode_least_s": sum(least(s) for s in steps),
        "least_s": least(pre) + sum(least(s) for s in steps),
    }
