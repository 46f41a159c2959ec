"""The control at a size a CPU test can hold: the reference with its
weights in float8 (the step below the configurations' bfloat16) must
fail each cell's output limit, where the program, in bfloat16, passes.

Size: each configuration's published widths with 4 layers and the
vocabulary cut to 32,768, one task of 4 prompts x 64 tokens + 64 greedy
tokens.  On the chip the same readings are taken at the cells' own sizes
by ``bench/calibrate.py --control`` (``PERF.md``)."""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

import repro.configs as cfgs
from repro.models import build
from repro.runtime.serve_loop import ServeConfig, make_generate_program
from bench.lib import reference, spec, weights

LAYERS, VOCAB, B, P, N = 4, 32768, 4, 64, 64


def _readings(cell_name: str, seed: int) -> dict:
    cell = spec.cell(cell_name)
    m = dataclasses.replace(cell.model, num_hidden_layers=LAYERS,
                            vocab_size=VOCAB)
    cfg = cfgs.get(cell.config["arch"]).replace(n_layers=LAYERS,
                                                vocab_size=VOCAB)
    params = jax.jit(weights.program_params, static_argnums=1)(
        weights.seed_words(seed), m)
    program = make_generate_program(
        build(cfg), ServeConfig(max_new_tokens=N, prompt_len=P,
                                batch_per_task=B), params)
    prompts = np.random.default_rng(seed).integers(0, VOCAB, (B, P),
                                                   dtype=np.int32)
    served = jax.jit(program.fn)(params, {"tokens": prompts})["generated"]
    return reference.served_gap(seed, m, prompts, np.asarray(served),
                                quantize=True) | {"limit": cell.check[
                                    "max_logit_gap"]}


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("cell_name", ["qwen3-gen-batch",
                                       "minicpm2b-gen-long"])
def test_control_fails_where_the_program_passes(cell_name, seed):
    r = _readings(cell_name, seed)
    assert r["max_gap"] < r["limit"] < r["control_gap"], r
