"""What a run is made of, found by name from ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. Each lives in a file of its
own, found by name: ``configs/<config>.json`` (sizes as run, in the keys of
the model's published ``config.json``), ``traffic/<traffic>.json``
(parameters for the one generator in ``traffic.py``) and, for each
per-layer metric, ``metrics/<metric>.py`` (a reader with ``read(ctx)``).
Adding a cell or a metric adds files and entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent

# config.json keys of the published models -> the sizes the harness uses
_EXPERT_KEYS = ("num_local_experts", "num_experts")
_EXPERT_WIDTH_KEYS = ("moe_intermediate_size", "intermediate_size")
_QK_NORM_TYPES = {"qwen3_moe": True, "mixtral": False}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one decoder-only MoE configuration, as run."""
    name: str
    vocab: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    n_experts: int
    top_k: int
    d_expert: int
    rms_eps: float
    rope_theta: float
    qk_norm: bool
    weight_seed: int


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    dims: Dims
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _first(cfg: dict, keys) -> int:
    for k in keys:
        if k in cfg:
            return cfg[k]
    raise KeyError(f"config has none of {keys}")


def dims_of(name: str, cfg: dict) -> Dims:
    if cfg.get("model_type") not in _QK_NORM_TYPES:
        raise ValueError(f"{name}: model_type {cfg.get('model_type')!r} has "
                         f"no reference here ({sorted(_QK_NORM_TYPES)})")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{name}: only silu experts are referenced")
    if cfg.get("sliding_window") not in (None, 0):
        raise ValueError(f"{name}: sliding-window attention is not served")
    return Dims(
        name=name, vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or
        cfg["hidden_size"] // cfg["num_attention_heads"],
        n_experts=_first(cfg, _EXPERT_KEYS),
        top_k=cfg["num_experts_per_tok"],
        d_expert=_first(cfg, _EXPERT_WIDTH_KEYS),
        rms_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]),
        qk_norm=_QK_NORM_TYPES[cfg["model_type"]],
        weight_seed=int(cfg["weight_seed"]))


def arch_config(dims: Dims, source: str = ""):
    """The program's ``ArchConfig`` for these sizes (every MoE layer
    routed, no shared experts)."""
    from repro.configs.base import ArchConfig
    return ArchConfig(
        name=dims.name, family="moe", n_layers=dims.n_layers,
        d_model=dims.d_model, n_heads=dims.n_heads,
        n_kv_heads=dims.n_kv_heads, d_ff=dims.d_expert, vocab=dims.vocab,
        head_dim=dims.head_dim, qk_norm=dims.qk_norm,
        rope_theta=dims.rope_theta, rms_eps=dims.rms_eps,
        n_experts=dims.n_experts, n_shared_experts=0, top_k=dims.top_k,
        d_expert=dims.d_expert, source=source)


def bench_dir(root: Path) -> Path:
    return root / "benchmarks" / "chip"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, workload: str) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its
    configuration, traffic and metric entries."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[w["config"]]
    cfg = load_json(root / centry["file"])
    traffic = load_json(bench_dir(root) / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])
                 and m["moves"] in moved]
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=cfg,
                dims=dims_of(w["config"], cfg), traffic_name=w["traffic"],
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def metric_reader(root: Path, name: str) -> Callable:
    """``read(ctx)`` of ``metrics/<name>.py`` under ``root``."""
    path = bench_dir(root) / "metrics" / f"{name}.py"
    mod_name = "chipbench_metric_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_of(device_kind: str, root: Optional[Path] = None) -> Dict:
    """Published peaks of one chip of ``device_kind``. A device that is
    not in the table is an error, never a default."""
    table = load_json((bench_dir(root) if root else BENCH_DIR)
                      / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(table)}")
    return table[device_kind]


def max_seq_of(traffic: dict) -> int:
    """KV rows per slot: the longest prompt and output, rounded up to
    128."""
    need = traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"] + 1
    return int(math.ceil(need / 128) * 128)
