"""``BENCHMARK.json`` against the shape the harness relies on: every
name it uses resolves to a file of its own under ``bench/``."""

from __future__ import annotations

import json
import os
import re

import pytest

from bench.lib import spec

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_resolves(w):
    cell = spec.cell(w["name"])
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert len(w["why"]) <= 200 and "\n" not in w["why"]
    assert w["chips"] in (1, 4)
    assert cell.check["max_logit_gap"] > 0
    e2e = spec.end_to_end_names(w["name"])
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.per_layer_names(w["name"])


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_every_config_file(c):
    with open(os.path.join(spec.ROOT, c["file"])) as f:
        cfg = json.load(f)
    assert cfg["source"] == c["source"]
    assert cfg["reduced"] == c["reduced"]
    assert os.path.exists(os.path.join(spec.ROOT, "bench", "models",
                                       f"{cfg['family']}.py"))
    assert set(c) == {"name", "source", "file", "reduced", "why"}


def test_metrics():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(spec.ROOT, "bench", "metrics",
                                           f"{m['name']}.py"))
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all(len(x) <= 200 for x in layers)


def test_cells_are_distinct():
    """Each pair of configuration and traffic is one cell, and at most half
    of the cells hold four chips."""
    cells = BENCH["workloads"]
    names = [w["name"] for w in cells]
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(names) == len(set(names))
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    used = {w["config"] for w in cells}
    assert used == {c["name"] for c in BENCH["configs"]}
