"""Operation and byte counts against counts made by hand from the
published sizes."""

from __future__ import annotations

import pytest

from bench.lib import flops, spec

QWEN = spec.cell("qwen3-gen-batch").model
MINICPM = spec.cell("minicpm2b-gen-long").model


@pytest.mark.parametrize("m,layer,weights,kv", [
    # 2048*128*(2*16 + 2*8) + 3*2048*6144; 28 layers + 151936*2048 table
    (QWEN, 50_331_648, 3_440_902_144, 114_688),
    # 2304*64*(2*36 + 2*36) + 3*2304*5760; 40 layers + 122753*2304 table
    (MINICPM, 61_046_784, 5_449_388_544, 368_640),
])
def test_sizes(m, layer, weights, kv):
    assert flops.layer_matmul_params(m) == layer
    assert flops.weight_bytes(m) == weights
    assert m.kv_bytes_per_token == kv


def test_qwen3_prefill_by_hand():
    f, b = flops.prefill(QWEN, 2, 4)
    dense = 2 * 2 * 4 * 28 * 50_331_648
    attn = 4 * 128 * 16 * 28 * (2 * (1 + 2 + 3 + 4))  # causal pairs
    head = 2 * 2 * 2048 * 151_936  # last position of each prompt
    assert f == dense + attn + head
    assert b == 3_440_902_144 + 2 * 4 * 114_688


def test_minicpm_decode_step_by_hand():
    # the token fed at position 10 sees 10 filled positions and itself
    f, b = flops.decode_step(MINICPM, 3, 10)
    dense = 2 * 3 * 40 * 61_046_784
    attn = 4 * 64 * 36 * 40 * 3 * 11
    head = 2 * 3 * 2304 * 122_753
    assert f == dense + attn + head
    assert b == 5_449_388_544 + 3 * 11 * 368_640


def test_task_counts_n_minus_one_steps_and_its_least_time():
    pf, bw = 197e12, 819e9
    t = flops.task(QWEN, 16, 512, 128, pf, bw)
    pre = flops.prefill(QWEN, 16, 512)
    steps = [flops.decode_step(QWEN, 16, 512 + j - 1) for j in range(1, 128)]
    assert t["flops"] == pre[0] + sum(s[0] for s in steps)
    assert t["bytes"] == pre[1] + sum(s[1] for s in steps)
    # prefill of 8192 tokens is bound by FLOPs, each decode step by bytes
    assert t["prefill_least_s"] == pre[0] / pf
    assert t["decode_least_s"] == pytest.approx(sum(s[1] for s in steps) / bw)
    assert 0.8 < t["least_s"] < 0.85
