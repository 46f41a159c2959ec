"""Benchmark cells cut to CPU-test size: the program at
``repro.configs.reduced`` widths (float32), the cell's own traffic kind
with small shapes."""

from __future__ import annotations

import dataclasses

import repro.configs as cfgs
from bench.lib import farm, spec

ROOT_DIR = spec.ROOT


def small_cell(name: str, *, loop: str = "closed", root: str = ROOT_DIR,
               **traffic) -> spec.Cell:
    """The cell ``name`` (of the checkout at ``root``) with its model at
    reduced widths, by its family's ``reduced``, and small traffic;
    :func:`patch_program` makes the program match."""
    real = spec.cell(name, root)
    model = real.family.reduced(reduced_config(real.config["arch"]))
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
