"""What one cell is: its entry in ``BENCHMARK.json`` and the files that
entry names.

A cell is found by name.  Its configuration is
``bench/configs/<config>.json``, its traffic mix
``bench/traffic/<traffic>.json`` and its output check
``bench/checks/<cell>.json``; nothing here knows any cell by name.
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass, field

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@dataclass(frozen=True)
class Model:
    """The sizes of a dense decoder, under the published config's keys."""

    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    vocab_size: int
    rms_norm_eps: float
    rope_theta: float
    qk_norm: bool = False
    scale_emb: float = 1.0
    scale_depth: float | None = None  # None: plain residual
    dim_model_base: int | None = None  # None: no logit scaling
    dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, cfg: dict) -> "Model":
        names = set(cls.__dataclass_fields__)
        kw = {k: v for k, v in cfg.items() if k in names}
        if "head_dim" not in kw:
            kw["head_dim"] = cfg["hidden_size"] // cfg["num_attention_heads"]
        if "torch_dtype" in cfg:
            kw["dtype"] = cfg["torch_dtype"]
        return cls(**kw)

    @property
    def residual_scale(self) -> float:
        if self.scale_depth is None:
            return 1.0
        return self.scale_depth / math.sqrt(self.num_hidden_layers)

    @property
    def logit_scale(self) -> float:
        if self.dim_model_base is None:
            return 1.0
        return self.dim_model_base / self.hidden_size

    @property
    def kv_bytes_per_token(self) -> int:
        """Keys and values of one position, all layers, in ``dtype``."""
        return (2 * self.num_hidden_layers * self.num_key_value_heads
                * self.head_dim * self.bytes_per_value)

    @property
    def bytes_per_value(self) -> int:
        return {"bfloat16": 2, "float16": 2, "float32": 4}[self.dtype]


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
    model: Model
    traffic_name: str
    traffic: Traffic
    check: dict  # limits of the output comparison


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


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
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, model=Model.from_config(config),
                traffic_name=w["traffic"],
                traffic=Traffic.from_file(traffic), check=check)


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
