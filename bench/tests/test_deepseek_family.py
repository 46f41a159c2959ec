"""The DeepSeek-V2 family (``bench/models/deepseek_v2.py``): it refuses
what it does not compute, its weights take the program's layout, its
costs are the hand counts, the cell runs correct at reduced size, and
the readers of its scopes find nothing in a program without them."""

from __future__ import annotations

import json
import os
import shutil
from types import SimpleNamespace

import jax
import pytest

import repro.configs as cfgs
from bench import run
from bench.lib import flops, op_scopes, spec, trace, weights
from bench.tests.cells import ROOT_DIR, patch_program, small_cell
from repro.models import build

FAMILY = spec.family("deepseek_v2")
CELL = "dsv2lite-gen-batch"
with open(os.path.join(ROOT_DIR, "bench", "configs",
                       "deepseek-v2-lite-ep4.json")) as _f:
    CONFIG = json.load(_f)

REFUSED = {
    "q_lora_rank": 1536,
    "scoring_func": "sigmoid",
    "topk_method": "group_limited_greedy",
    "n_group": 8,
    "topk_group": 3,
    "moe_layer_freq": 2,
    "rope_scaling": {"type": "linear", "factor": 4.0},
    "attention_bias": True,
    "hidden_act": "gelu",
    "tie_word_embeddings": True,
    "routed_scaling_factor": 16,  # DeepSeek-V2's
    "num_key_value_heads": 8,
    "n_routed_experts": 80,  # more than the router's 64
    "use_sliding_window": True,  # a key the family does not model
}


@pytest.mark.parametrize("key", sorted(REFUSED))
def test_refuses_what_it_does_not_compute(key):
    FAMILY.Model.from_config(CONFIG)
    with pytest.raises(ValueError, match=key):
        FAMILY.Model.from_config(CONFIG | {key: REFUSED[key]})


def test_refuses_plain_rope():
    with pytest.raises(ValueError, match="rope_scaling"):
        FAMILY.Model.from_config(CONFIG | {"rope_scaling": None})


def test_the_file_is_the_published_config_with_the_share():
    m = spec.cell(CELL).model
    assert (m.n_routed_experts, m.router_experts, m.held_experts_offset) == (
        16, 64, 0)
    assert CONFIG["reduced"] == ["n_routed_experts"]
    assert m.rope_scaling.factor == 40 and m.rope_scaling.mscale == 0.707
    assert m.kv_bytes_per_token == 31_104


@pytest.mark.parametrize("size", ["full", "reduced"])
def test_weights_take_the_programs_layout(size):
    cfg = cfgs.get("deepseek_v2_lite_ep4")
    m = spec.cell(CELL).model
    if size == "reduced":
        cfg = cfgs.reduced(cfg)
        m = FAMILY.reduced(cfg)
    api = build(cfg)
    weights.check_layout(jax.eval_shape(api.init, jax.random.PRNGKey(0)),
                         FAMILY, m)


def test_the_program_matches_the_file():
    cfg = cfgs.get("deepseek_v2_lite_ep4")
    pairs = FAMILY.program_pairs(spec.cell(CELL).model)
    assert {k: v for k, v in pairs.items() if getattr(cfg, k) != v} == {}
    wrong = cfg.replace(moe=cfg.moe.__class__(n_experts=64, top_k=6))
    assert getattr(wrong, "moe") != pairs["moe"]


# a small model whose costs are counted by hand below
SMALL = FAMILY.Model(
    hidden_size=8, intermediate_size=20, moe_intermediate_size=6,
    num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=2,
    kv_lora_rank=4, qk_nope_head_dim=3, qk_rope_head_dim=2, v_head_dim=5,
    n_routed_experts=2, router_experts=8, held_experts_offset=4,
    num_experts_per_tok=4, n_shared_experts=2, norm_topk_prob=False,
    vocab_size=50, rms_norm_eps=1e-6, rope_theta=10000.0,
    rope_scaling=FAMILY.Yarn(40, 4096, 32, 1, 0.707, 0.707))


def test_costs_by_hand():
    # attention: wq 8*2*5 + wkv_a 8*(4+2) + wkv_b 4*2*(3+5) + wo 2*5*8
    attn = 80 + 48 + 64 + 80
    assert FAMILY.attention_params(SMALL) == attn
    expert = 3 * 8 * 6
    # layer 0 dense 3*8*20; 2 MoE layers: router 8*8 (float32), 2 held
    # experts and the shared SwiGLU of 12; the head 50*8
    weights_ = (3 * attn * 2 + 3 * 8 * 20 * 2
                + 2 * ((2 * expert + 3 * 8 * 12) * 2 + 8 * 8 * 4) + 50 * 8 * 2)
    assert FAMILY.weight_bytes(SMALL) == weights_
    kv = 3 * (4 + 2) * 2
    # per token: 4 choices at the held share 2/8 of one expert each
    token = 2 * (3 * attn + 3 * 8 * 20 + 2 * (8 * 8 + 4 * 0.25 * expert
                                              + 3 * 8 * 12))
    f, b = FAMILY.prefill(SMALL, 2, 3)
    pairs = 2 * (1 + 2 + 3)
    assert f == 2 * 3 * token + 2 * 2 * (5 + 5) * 3 * pairs + 2 * 2 * 8 * 50
    assert b == weights_ + 2 * 3 * kv
    # the token at position 3 sees 3 filled positions and itself; absorbed
    # scores over latent + rope key, and the sum over the latent
    f, b = FAMILY.decode_step(SMALL, 2, 3)
    assert f == 2 * token + 2 * 2 * (4 + 2 + 4) * 3 * (2 * 4) + 2 * 2 * 8 * 50
    assert b == weights_ + 2 * 4 * kv
    x = FAMILY.task_extras(SMALL, 2, 3, 4, 100.0, 10.0)
    assert x["expert_decode_step_bytes"] == 2 * 2 * expert * 2
    assert x["expert_decode_step_flops"] == 2 * 2 * 4 * 0.25 * expert * 2
    assert x["expert_prefill_flops"] == 3 * x["expert_decode_step_flops"]
    assert x["expert_decode_step_least_s"] == 2 * 2 * expert * 2 / 10.0
    t = flops.task(FAMILY, SMALL, 2, 3, 4, 100.0, 10.0)
    assert t["expert_prefill_least_s"] == x["expert_prefill_least_s"]


def test_the_cell_is_correct_at_reduced_size(monkeypatch):
    cell = small_cell(CELL)
    patch_program(monkeypatch, cell)
    assert cell.model.n_routed_experts < cell.model.router_experts
    res = run.run_cell(cell, 2**31 + 11, 1.0, False, jax.devices()[:1],
                       0.0)
    assert res["correct"], res["check"]
    assert res["check"]["max_logit_gap"]["value"] == pytest.approx(0,
                                                                   abs=1e-4)
    assert set(res["metrics"]) == {"gen_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("tf_op,segments", [
    ("jit(generate)/decode/while/body/closed_call/experts/ragged_dot:",
     {"decode", "experts"}),
    ("jit(generate)/prefill/while/body/shared_experts/dot_general:",
     {"prefill", "shared_experts"}),
    ("jit(generate)/vmap(decode)/while/body/mla/dot_general:",
     {"decode", "mla"}),
    (None, set()),
])
def test_segments_of_a_name_stack(tf_op, segments):
    got = op_scopes.segments(tf_op)
    assert segments <= got
    assert "experts" not in got or "shared_experts" not in segments


def test_readers_find_nothing_in_a_program_without_the_scopes(tmp_path):
    shutil.copy(os.path.join(ROOT_DIR, "bench", "tests", "data",
                             "small.xplane.pb"), tmp_path / "small.xplane.pb")
    view = SimpleNamespace(
        cell=SimpleNamespace(name="small", root=ROOT_DIR,
                             traffic=SimpleNamespace(new_tokens=8)),
        trace=trace.reduce(str(tmp_path)), trace_dir=str(tmp_path),
        task_cost={"expert_decode_step_least_s": 1e-3})
    for name in ("expert_step_ms.gen", "expert_roofline", "mla_step_ms.gen"):
        assert run.read_metric(name, view) is None


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_where_the_program_passes(seed):
    """``test_control.py``'s readings for this cell: published widths, 4
    layers (the dense one and 3 of experts), vocabulary 32,768."""
    from bench.tests.test_control import _readings

    r = _readings(CELL, seed)
    assert r["max_gap"] < r["limit"] < r["control_gap"], r
