"""Model families (``bench/models/<family>.py``): the dense family gives
what the harness gave before its code moved there, bit for bit; it
refuses a configuration it cannot compute; and a family that is new
files only enters the benchmark without an edit to any existing file."""

from __future__ import annotations

import hashlib
import json
import os
import shutil

import jax
import numpy as np
import pytest

from bench import run
from bench.lib import flops, peaks, spec, weights
from bench.tests.cells import ROOT_DIR, patch_program, small_cell

DENSE = spec.family("dense")
with open(os.path.join(os.path.dirname(__file__), "data",
                       "dense_parent.json")) as _f:
    PARENT = json.load(_f)


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(str((a.shape, a.dtype.str)).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(PARENT["tiny"]))
def test_dense_weights_and_reference_match_the_parent(name):
    want, seed = PARENT["tiny"][name], PARENT["seed"]
    m = DENSE.Model(**want["model"])
    params = jax.jit(DENSE.program_params, static_argnums=1)(
        weights.seed_words(seed), m)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    assert [jax.tree_util.keystr(k) for k, _ in leaves] == want["paths"]
    assert _digest([a for _, a in leaves]) == want["params"]
    tokens = np.random.default_rng(0).integers(0, m.vocab_size, (2, 8),
                                               dtype=np.int32)
    pos = np.arange(8)
    assert _digest([DENSE.logits(seed, m, tokens, pos)]) == want["logits"]
    assert _digest([DENSE.logits(seed, m, tokens, pos, quantize=True)]) == (
        want["control_logits"])


@pytest.mark.parametrize("name", sorted(PARENT["task"]))
def test_dense_task_costs_match_the_parent(name):
    cell = spec.cell(name)
    t, p = cell.traffic, peaks.peaks("TPU v5 lite")
    got = flops.task(cell.family, cell.model, t.prompts_per_task,
                     t.prompt_len, t.new_tokens, p["flops_bf16"],
                     p["hbm_bytes_per_s"])
    assert got == PARENT["task"][name]


REFUSED = {
    "kv_lora_rank": 512,
    "n_routed_experts": 64,
    "rope_scaling": {"type": "yarn", "factor": 4.0},
    "attention_bias": True,
    "use_sliding_window": True,
    "hidden_act": "gelu",
    "tie_word_embeddings": False,
}


@pytest.mark.parametrize("key", sorted(REFUSED))
def test_dense_refuses_what_it_does_not_compute(key):
    with open(os.path.join(ROOT_DIR, "bench", "configs",
                           "qwen3-1.7b.json")) as f:
        cfg = json.load(f)
    DENSE.Model.from_config(cfg)
    with pytest.raises(ValueError, match=key):
        DENSE.Model.from_config(cfg | {key: REFUSED[key]})


def _twin_checkout(tmp) -> str:
    """A checkout with a copy of the dense family under a new name, and
    a configuration, traffic mix, check and cell that use it: new files
    and new ``BENCHMARK.json`` entries only."""
    def copy(src, dst):
        os.makedirs(os.path.dirname(os.path.join(tmp, dst)), exist_ok=True)
        shutil.copy(os.path.join(ROOT_DIR, src), os.path.join(tmp, dst))

    copy("bench/models/dense.py", "bench/models/twin.py")
    copy("bench/traffic/gen-batch.json", "bench/traffic/twin-batch.json")
    copy("bench/checks/qwen3-gen-batch.json", "bench/checks/twin-gen.json")
    with open(os.path.join(ROOT_DIR, "bench/configs/qwen3-1.7b.json")) as f:
        cfg = json.load(f)
    os.makedirs(os.path.join(tmp, "bench", "configs"))
    with open(os.path.join(tmp, "bench/configs/twin.json"), "w") as f:
        json.dump(cfg | {"family": "twin"}, f)
    bench = spec.benchmark()
    bench["configs"].append({"name": "twin", "source": cfg["source"],
                             "file": "bench/configs/twin.json",
                             "reduced": [], "why": "a family of new files"})
    bench["workloads"].append({"name": "twin-gen", "config": "twin",
                               "traffic": "twin-batch", "chips": 1,
                               "why": "the twin family through the farm"})
    for m in bench["end_to_end"]:
        if m["name"] == "gen_tokens_per_s":
            m["workloads"].append("twin-gen")
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return str(tmp)


def test_a_new_family_enters_as_new_files(tmp_path, monkeypatch):
    root = _twin_checkout(tmp_path)
    cell = spec.cell("twin-gen", root)
    assert cell.family is spec.family("twin", root)
    assert cell.family is not DENSE
    assert cell.family.__file__ == os.path.join(root, "bench", "models",
                                                "twin.py")
    assert isinstance(cell.model, cell.family.Model)
    small = small_cell("twin-gen", root=root)
    patch_program(monkeypatch, small)
    res = run.run_cell(small, 2**31 + 17, 1.0, False, jax.devices()[:1],
                       0.0)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"gen_tokens_per_s", "setup_s"}
