"""The timed path broken underneath a whole run (the chip check skipped,
reduced sizes on the CPU): each fault that a served cell can have must
turn ``correct`` false."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import repro.runtime.serve_loop as serve_loop
from repro.core import Program, Service
from bench import run
from bench.tests.cells import patch_program, small_cell


def _break_program(monkeypatch, change):
    """``change(generated, payload)`` rewrites every task's tokens where
    the program produces them."""
    make = serve_loop.make_generate_program

    def broken(api, sc, params):
        good = make(api, sc, params)

        def generate(params, payload):
            out = good.fn(params, payload)
            return {"generated": change(out["generated"], payload, api.cfg)}
        return Program(generate, name=good.name, resident=params)
    monkeypatch.setattr(serve_loop, "make_generate_program", broken)


def _token_altered(gen, payload, cfg):
    return gen.at[:, gen.shape[1] // 2].set(
        (gen[:, gen.shape[1] // 2] + 1) % cfg.vocab_size)


def _half_batch_left_out(gen, payload, cfg):
    half = gen.shape[0] // 2
    return jnp.concatenate([gen[:half], gen[:half]])


def _rows_swapped(gen, payload, cfg):
    return jnp.roll(gen, 1, axis=0)


def _previous_answer(monkeypatch):
    """The farm hands each task the answer of the task before it."""
    last = {}
    execute = Service.execute

    def shifted(self, program, payload):
        out = execute(self, program, payload)
        prev = last.get(id(self), out)
        last[id(self)] = out
        return prev
    monkeypatch.setattr(Service, "execute", shifted)


CASES = {
    "token_altered": lambda mp: _break_program(mp, _token_altered),
    "half_batch_left_out": lambda mp: _break_program(mp,
                                                     _half_batch_left_out),
    "answers_swapped_in_task": lambda mp: _break_program(mp, _rows_swapped),
    "answer_of_another_task": _previous_answer,
}


@pytest.mark.parametrize("fault", sorted(CASES))
@pytest.mark.parametrize("name", ["qwen3-gen-batch", "minicpm2b-gen-long"])
def test_fault_fails_the_check(monkeypatch, name, fault):
    cell = small_cell(name)
    patch_program(monkeypatch, cell)
    CASES[fault](monkeypatch)
    res = run.run_cell(cell, 2**31 + 11, 1.0, False, jax.devices()[:1], 0.0)
    assert not res["correct"], res["check"]
    gap = res["check"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_token_altered_in_the_open_loop_fails(monkeypatch):
    cell = small_cell("qwen3-chat-open", loop="open", prompts_per_task=1,
                      check_rows_per_task=1, farm={"max_batch": 4})
    patch_program(monkeypatch, cell)
    _break_program(monkeypatch, _token_altered)
    res = run.run_cell(cell, 2**31 + 13, 1.0, False, jax.devices()[:1], 0.0)
    assert not res["correct"], res["check"]
