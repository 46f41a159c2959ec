"""Operations and bytes that one generation task cannot avoid, from shapes.

A task is ``B`` prompts of ``P`` tokens and ``N`` greedy new tokens:
prefill over the prompts yields token 0, then ``N - 1`` decode steps
yield tokens 1..N-1.  Each model family (``bench/models/<family>.py``)
counts its own phases, ``prefill(m, B, P)`` and ``decode_step(m, B,
filled)``, by these rules, and nothing else:

- every matmul weight, and the embedding table or head as the LM head,
  is read once per prefill and once per decode step;
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


def task(family, m, B: int, P: int, N: int, peak_flops: float,
         peak_bytes_per_s: float) -> dict:
    """FLOPs, bytes and the least time of one task on a chip with the
    given peaks: each phase takes the larger of its FLOPs over the peak
    rate and its bytes over the memory bandwidth.  A family that defines
    ``task_extras(m, B, P, N, peak_flops, peak_bytes_per_s)`` adds the
    keys of the dict it returns (say, an expert phase's least time) for
    its own metric readers; they cannot replace the keys counted here."""
    def least(fb):
        return max(fb[0] / peak_flops, fb[1] / peak_bytes_per_s)

    pre = family.prefill(m, B, P)
    steps = [family.decode_step(m, B, P + j - 1) for j in range(1, N)]
    out = {
        "flops": pre[0] + sum(f for f, _ in steps),
        "bytes": pre[1] + sum(b for _, b in steps),
        "prefill_least_s": least(pre),
        "decode_least_s": sum(least(s) for s in steps),
        "least_s": least(pre) + sum(least(s) for s in steps),
    }
    extras = getattr(family, "task_extras", None)
    if extras is None:
        return out
    return extras(m, B, P, N, peak_flops, peak_bytes_per_s) | out
