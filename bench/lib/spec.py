"""What one cell is: its entry in ``BENCHMARK.json`` and the files that
entry names.

A cell is found by name.  Its configuration is the file its
``configs`` entry names (``bench/configs/<config>.json``), whose
``"family"`` key names the model family ``bench/models/<family>.py``;
its traffic mix is ``bench/traffic/<traffic>.json`` and its output check
``bench/checks/<cell>.json``.  Nothing here knows any cell, or any
model's sizes: a family module brings its ``Model`` and everything that
reads it.
"""

from __future__ import annotations

import functools
import hashlib
import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field
from types import ModuleType

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@dataclass(frozen=True)
class Traffic:
    """A traffic mix: one general generator reads these parameters."""

    loop: str  # "closed" | "open"
    prompts_per_task: int
    prompt_len: int
    new_tokens: int
    services_per_chip: int
    farm: dict = field(default_factory=dict)
    rate_per_s: float = 0.0  # open loop: mean arrival rate
    trace_seconds: float = 0.0  # traced part of the window; 0: all
    check_tasks_per_service: int = 2  # tasks of each service checked
    check_rows_per_task: int = 1  # prompts of each checked task

    @classmethod
    def from_file(cls, d: dict) -> "Traffic":
        names = set(cls.__dataclass_fields__)
        return cls(**{k: v for k, v in d.items() if k in names})

    @property
    def tokens_per_task(self) -> int:
        return self.prompts_per_task * self.new_tokens


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict  # the configuration file as it is run
    family: ModuleType  # bench/models/<family>.py
    model: object  # family.Model.from_config(config)
    traffic_name: str
    traffic: Traffic
    check: dict  # limits of the output comparison
    root: str = ROOT  # the checkout the cell's files were read from


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@functools.cache
def family(name: str, root: str = ROOT) -> ModuleType:
    """The model family ``bench/models/<name>.py`` under ``root``, loaded
    by path once, as a module of its own per path (dataclasses find
    their module in ``sys.modules``)."""
    path = os.path.join(root, "bench", "models", f"{name}.py")
    key = hashlib.sha256(os.path.abspath(path).encode()).hexdigest()[:12]
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_family_{key}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[mod_spec.name] = mod
    mod_spec.loader.exec_module(mod)
    return mod


def benchmark(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def cell(name: str, root: str = ROOT) -> Cell:
    """The cell called ``name``, with every file its entry names."""
    bench = benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _load(os.path.join(root, conf["file"]))
    traffic = _load(os.path.join(root, "bench", "traffic",
                                 f"{w['traffic']}.json"))
    check = _load(os.path.join(root, "bench", "checks", f"{name}.json"))
    fam = family(config["family"], root)
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, family=fam,
                model=fam.Model.from_config(config),
                traffic_name=w["traffic"],
                traffic=Traffic.from_file(traffic), check=check, root=root)


def end_to_end_names(name: str, root: str = ROOT) -> list[str]:
    """The end-to-end metrics that the cell ``name`` reports."""
    return [m["name"] for m in benchmark(root)["end_to_end"]
            if name in m.get("workloads", [name])]


def per_layer_names(name: str, root: str = ROOT) -> list[dict]:
    """The per-layer metrics read in the cell ``name``."""
    bench = benchmark(root)
    e2e = set(end_to_end_names(name, root))
    return [m for m in bench["per_layer"]
            if name in m.get("workloads", [name]) and m["moves"] in e2e]
