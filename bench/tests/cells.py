"""Benchmark cells cut to CPU-test size: the program at
``repro.configs.reduced`` widths (float32), the cell's own traffic kind
with small shapes."""

from __future__ import annotations

import dataclasses

import repro.configs as cfgs
from bench.lib import farm, spec

ROOT_DIR = spec.ROOT


def small_cell(name: str, *, loop: str = "closed", **traffic) -> spec.Cell:
    """The cell ``name`` with its model at reduced widths and small
    traffic; :func:`patch_program` makes the program match."""
    real = spec.cell(name)
    cfg = reduced_config(real.config["arch"])
    model = spec.Model(
        hidden_size=cfg.d_model, intermediate_size=cfg.d_ff,
        num_hidden_layers=cfg.n_layers, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        vocab_size=cfg.vocab_size, rms_norm_eps=1e-6,
        rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm, dtype="float32")
    kw = dict(loop=loop, prompts_per_task=2, prompt_len=16, new_tokens=6,
              services_per_chip=2, farm={"max_batch": 1, "max_inflight": 1},
              trace_seconds=0.5, check_tasks_per_service=2,
              check_rows_per_task=2,
              rate_per_s=40.0)
    kw.update(traffic)
    return dataclasses.replace(real, model=model,
                               traffic=spec.Traffic(**kw))


def reduced_config(arch: str):
    return cfgs.reduced(cfgs.get(arch))


def patch_program(monkeypatch, cell: spec.Cell) -> None:
    """Serve the reduced program in place of the full-width one."""
    cfg = reduced_config(cell.config["arch"])
    monkeypatch.setattr(farm, "program_config", lambda _cell: cfg)
