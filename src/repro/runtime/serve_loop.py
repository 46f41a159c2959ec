"""Serving as a task farm — the paper's workload, verbatim.

Batched generation requests are *embarrassingly parallel*: each task is
(prompt batch -> generated tokens), no cross-task state.  The farm:

    program  = prefill + N decode steps (ONE jit program per task)
    services = pods running the compiled program
    client   = BasicClient / FarmExecutor with pull scheduling, elastic
               recruitment and rescheduling of failed requests

This module builds the per-task generation program for any registry model.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import BasicClient, FarmExecutor, Program
from repro.models.registry import ModelAPI


@dataclass(frozen=True)
class ServeConfig:
    max_new_tokens: int = 8
    prompt_len: int = 16
    batch_per_task: int = 4
    greedy: bool = True


def make_generate_program(api: ModelAPI, sc: ServeConfig, params) -> Program:
    """payload: {"tokens": (B, prompt_len)} -> {"generated": (B, N)}.

    ``params`` are the program's resident state (weights are resident on
    the service; the task payload is only the request batch — matching
    JJPF, where the program ships once at recruit time and tasks stay
    small).  ``program.fn(params, payload)`` is the generate function."""
    cfg = api.cfg
    budget = sc.prompt_len + sc.max_new_tokens

    def generate(params, payload):
        tokens = payload["tokens"]
        # the scopes name the phases' ops in the device trace
        with jax.named_scope("prefill"):
            logits, caches = api.prefill(params, payload, seq_budget=budget)

        def step(carry, i):
            logits, caches = carry
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            batch = {"tokens": nxt, "cache_index": sc.prompt_len + i}
            logits, caches = api.decode(params, batch, caches)
            return (logits, caches), nxt[:, 0]

        with jax.named_scope("decode"):
            (_, _), toks = jax.lax.scan(step, (logits, caches),
                                        jnp.arange(sc.max_new_tokens))
        return {"generated": toks.T}  # (B, N)

    return Program(generate, name=f"generate[{cfg.name}]", resident=params)


def serve_requests(program: Program, prompts, sc: ServeConfig, *,
                   lookup, timeout: float = 300.0):
    """Partition ``prompts`` (N, prompt_len) into farm tasks and run them
    with ``program`` (from :func:`make_generate_program`).

    Returns (per-task results in prompt order, farm stats).  Each result
    stays on the device of the service that computed it;
    :func:`generated_tokens` gathers them on the host.  Returns only when
    no speculative duplicate of a task still runs, so the next call (or
    the process's exit) finds the devices free."""
    n = prompts.shape[0]
    bs = sc.batch_per_task
    tasks = [{"tokens": np.asarray(prompts[i:i + bs], np.int32)}
             for i in range(0, n, bs)]
    out: list = []
    client = BasicClient(program, None, tasks, out, lookup=lookup)
    client.compute(timeout=timeout)
    client.join(timeout)
    return out, client.stats()


def generated_tokens(results) -> np.ndarray:
    """(N, max_new_tokens) on the host, from results on any devices."""
    return np.concatenate([np.asarray(r["generated"]) for r in results])
