"""Benchmark data files, found by name.

A configuration is ``bench/configs/<name>.json`` (published key names,
as run), a traffic mix is ``bench/traffic/<name>.json``, a per-layer
metric is ``bench/metrics/<name>.py``, and a cell is an entry of
``workloads`` in ``BENCHMARK.json`` that names a configuration and a
mix.  Nothing here knows any particular cell.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

#: published config key -> the program's ModelConfig field
_PROGRAM_FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}
_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}


@dataclass(frozen=True)
class ModelSpec:
    """The sizes the yardstick (weights, reference, FLOP counts) reads."""
    name: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    dtype: str

    @classmethod
    def from_file(cls, c: Dict[str, Any]) -> "ModelSpec":
        if c.get("hidden_act") != "silu":
            raise ValueError(f"{c['name']}: only SwiGLU (silu) models")
        if c["tie_word_embeddings"]:
            raise ValueError(f"{c['name']}: tied embeddings are not "
                             f"supported yet (no cell needs them)")
        return cls(
            name=c["name"], layers=c["num_hidden_layers"],
            d_model=c["hidden_size"], heads=c["num_attention_heads"],
            kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            d_ff=c["intermediate_size"], vocab=c["vocab_size"],
            rope_theta=float(c["rope_theta"]),
            norm_eps=float(c["rms_norm_eps"]),
            dtype=_DTYPES[c["torch_dtype"]],
        )


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    spec: ModelSpec
    per_layer: List[Dict[str, Any]]
    end_to_end: List[Dict[str, Any]]


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return load_json(root / "BENCHMARK.json")


def config_file(name: str, bench: Path = BENCH) -> Dict[str, Any]:
    return load_json(bench / "configs" / f"{name}.json")


def traffic_file(name: str, bench: Path = BENCH) -> Dict[str, Any]:
    return load_json(bench / "traffic" / f"{name}.json")


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: Path = ROOT) -> Cell:
    b = benchmark(root)
    entries = [w for w in b["workloads"] if w["name"] == name]
    if not entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = entries[0]
    cfg = config_file(w["config"], root / "bench")
    return Cell(
        name=name, chips=int(w["chips"]), config=cfg,
        traffic=traffic_file(w["traffic"], root / "bench"),
        spec=ModelSpec.from_file(cfg),
        per_layer=[m for m in b["per_layer"] if _applies(m, name)],
        end_to_end=[m for m in b["end_to_end"] if _applies(m, name)],
    )


def program_config(c: Dict[str, Any]):
    """The program's ModelConfig for a configuration file: the registry
    entry named by ``arch`` with every size replaced by the file's."""
    from repro.configs.registry import get_config

    base = get_config(c["arch"])
    kw = {field: c[key] for key, field in _PROGRAM_FIELDS.items()}
    kw["dtype"] = _DTYPES[c["torch_dtype"]]
    kw["name"] = c["name"]
    kw["griffin"] = bool(c["serving"]["griffin"])
    return base.replace(**kw)


def metric_module(name: str, bench: Path = BENCH):
    """``bench/metrics/<name>.py``: its ``read(run) -> float | None``
    and, for a kernel's metric, ``KERNELS`` (name -> trace patterns)."""
    path = bench / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod
