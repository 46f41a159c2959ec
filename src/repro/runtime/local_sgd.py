"""Farm-mode training: the paper's task-parallel model applied to SGD.

Synchronous data-parallel training all-reduces every step — *not* a JJPF
workload.  Farm-mode makes training a stream of **independent tasks**:

    task(r, i) = "starting from the round-r parameters, run H optimizer
                  steps on deterministic data shard i, return the delta"

Within a round, tasks are independent -> they are farmed over the recruited
services (pods) with JJPF's pull scheduling, rescheduling on faults and
speculative re-execution of stragglers; the client merges deltas with an
outer optimizer (Nesterov momentum, à la DiLoCo/local-SGD) and starts the
next round.  Between syncs the pods exchange **nothing** — exactly the
paper's "no particular requirement in terms of data exchange" premise, so
commodity inter-pod links (DCN) suffice; fast ICI is only needed *inside*
a pod, where the per-task program itself is pjit-sharded.

Every task's data is a pure function of (seed, round, shard, step), so a
rescheduled task recomputes bit-identical gradients — fault tolerance is
exact, not approximate.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import BasicClient, Program
from repro.models.registry import ModelAPI
from repro.optim import adamw_update, init_opt_state
from .train_loop import TrainConfig, make_lr_fn


@dataclass(frozen=True)
class LocalSGDConfig:
    inner_steps: int = 4  # H
    outer_lr: float = 0.7
    outer_momentum: float = 0.9  # Nesterov outer optimizer (DiLoCo)
    n_shards: int = 4  # tasks per round
    batch_per_shard: int = 8
    seq_len: int = 64


def _synthetic_batch(key, perm, batch, seq_len, noise=0.05):
    """In-jit Markov batch (matches data.MarkovDataset semantics)."""
    V = perm.shape[0]
    k1, k2, k3 = jax.random.split(key, 3)
    first = jax.random.randint(k1, (batch,), 0, V)
    flips = jax.random.bernoulli(k2, noise, (batch, seq_len))
    rand = jax.random.randint(k3, (batch, seq_len), 0, V)

    def step(cur, inp):
        flip, r = inp
        nxt = jnp.where(flip, r, perm[cur])
        return nxt, nxt

    _, seq = jax.lax.scan(step, first, (flips.T, rand.T))
    toks = jnp.concatenate([first[:, None], seq.T], axis=1)  # (B, S+1)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


def make_local_round_program(api: ModelAPI, tc: TrainConfig,
                             ls: LocalSGDConfig, perm) -> Program:
    """The ProcessIf: payload {params, round, shard} -> {delta, loss}."""
    lr_fn = make_lr_fn(tc)
    cfg = api.cfg
    perm = jnp.asarray(perm)

    def run_round(payload):
        params0 = payload["params"]
        rnd = payload["round"]
        shard = payload["shard"]
        opt = init_opt_state(params0, moment_dtype=cfg.opt_state_dtype)

        def inner(carry, h):
            params, opt = carry
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(tc.seed), rnd * 131 + h),
                shard)
            batch = _synthetic_batch(key, perm, ls.batch_per_shard, ls.seq_len)
            (loss, _), grads = jax.value_and_grad(
                lambda p: api.train_loss(p, batch), has_aux=True)(params)
            step_no = rnd * ls.inner_steps + h
            params, opt, _ = adamw_update(
                grads, opt, params, lr=lr_fn(step_no),
                weight_decay=tc.weight_decay,
                moment_dtype=cfg.opt_state_dtype, clip_norm=tc.clip_norm)
            return (params, opt), loss

        (params, _), losses = jax.lax.scan(
            inner, (params0, opt), jnp.arange(ls.inner_steps))
        delta = jax.tree_util.tree_map(
            lambda a, b: (a.astype(jnp.float32) - b.astype(jnp.float32)),
            params, params0)
        return {"delta": delta, "loss": jnp.mean(losses)}

    return Program(run_round, name="local_sgd_round")


class LocalSGDTrainer:
    """The farm-mode driver (client side)."""

    def __init__(self, api: ModelAPI, tc: TrainConfig, ls: LocalSGDConfig,
                 *, lookup, seed: int = 0):
        self.api = api
        self.tc = tc
        self.ls = ls
        self.lookup = lookup
        import numpy as np

        rng = np.random.default_rng(seed)
        self.perm = rng.permutation(api.cfg.vocab_size).astype("int32")
        self.program = make_local_round_program(api, tc, ls, self.perm)
        self.params = api.init(jax.random.PRNGKey(tc.seed))
        self.outer_velocity = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), self.params)
        self.round = 0
        self.loss_history: list[float] = []
        self.farm_stats: list[dict] = []

    def run_round(self, *, timeout: float = 300.0) -> float:
        tasks = [{"params": self.params, "round": jnp.asarray(self.round),
                  "shard": jnp.asarray(i)} for i in range(self.ls.n_shards)]
        out: list[Any] = []
        client = BasicClient(self.program, None, tasks, out,
                             lookup=self.lookup, lease_s=60.0)
        client.compute(timeout=timeout)
        client.join(timeout)
        self.farm_stats.append(client.stats())
        # merge: average deltas, Nesterov outer step
        avg = jax.tree_util.tree_map(
            lambda *ds: sum(ds) / len(ds), *[o["delta"] for o in out])
        mu, lr = self.ls.outer_momentum, self.ls.outer_lr
        self.outer_velocity = jax.tree_util.tree_map(
            lambda v, d: mu * v + d, self.outer_velocity, avg)
        self.params = jax.tree_util.tree_map(
            lambda p, v, d: (p.astype(jnp.float32) + lr * (mu * v + d)
                             ).astype(p.dtype),
            self.params, self.outer_velocity, avg)
        self.round += 1
        loss = float(jnp.mean(jnp.stack([o["loss"] for o in out])))
        self.loss_history.append(loss)
        return loss

    def run(self, n_rounds: int, **kw) -> list[float]:
        for _ in range(n_rounds):
            self.run_round(**kw)
        return self.loss_history
