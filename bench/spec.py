"""Benchmark data files, found by name.

A configuration is ``bench/configs/<name>.json`` (published key names,
as run), a traffic mix is ``bench/traffic/<name>.json``, a per-layer
metric is ``bench/metrics/<name>.py``, and a cell is an entry of
``workloads`` in ``BENCHMARK.json`` that names a configuration and a
mix.  Each configuration names its architecture's yardstick,
``bench/arch/<yardstick>.py``, which holds everything computed from the
model's shapes.  Nothing here knows any particular cell or architecture.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    #: the configuration's yardstick module (:func:`yardstick`)
    arch: ModuleType
    #: its sizes, as the yardstick's ``ModelSpec`` reads them from ``config``
    spec: Any
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


def _load(path: Path, name: str) -> ModuleType:
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod  # a dataclass looks its module up
    mod_spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _yardstick_file(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no yardstick {path.stem!r}: {path}")
    return _load(path, f"bench_arch_{path.stem}")


def yardstick(config: Dict[str, Any], bench: Path = BENCH) -> ModuleType:
    """``bench/arch/<name>.py`` for a configuration's required
    ``"yardstick": "<name>"`` key, loaded once per file, so that every
    cell of a configuration shares its jitted entries.  The module holds
    everything the benchmark computes from the model's shapes:

    - ``ModelSpec.from_file(config) -> spec``: the sizes it reads
      (hashable, since the jitted entries take it as a static argument),
      refusing a file it cannot serve; a spec has ``layers``, the layers
      a step runs, and ``vocab``, the token ids the traffic may draw;
    - ``program_config(config)``: the program's ``ModelConfig``;
    - ``make(spec, key) -> weights``: the weights from the seed's key,
      on the device in one jitted call;
    - ``program_params(weights, program_config) -> tree``: the
      program's parameter tree over those arrays;
    - ``keep(spec, tier)``: what GRIFFIN keeps at a tier below 1.0, as
      ``gaps`` and ``token_flops`` take it (a count, or one per FF kind);
    - ``gaps(spec, weights, tokens, n_prompt, n_total, k, control)``:
      the plain reference's gap of each served token, and with
      ``control`` the gap of the token its int8 control ranks first
      (``k`` None at tier 1.0);
    - ``token_flops(spec, ctx, k) -> float``: model FLOPs of one token
      attending ``ctx`` keys, ``k`` from ``keep`` (``keep(spec, 1.0)``
      for the dense model);
    - ``kept(spec, weights, tokens, n_prompt, k)``: what the reference
      keeps for a prompt (read by ``bench/calibrate.py`` alone);
    - ``KERNEL_COUNTS``: kernel name -> ``(spec, pos, S) -> (flops,
      bytes)`` of one call for one request row and one layer.  A kernel
      that the architecture does not run has no entry, and its reader
      reads nothing.
    """
    name = config.get("yardstick")
    if not name:
        raise KeyError(f"{config.get('name')}: the configuration names no "
                       f"yardstick (its \"yardstick\" key)")
    return _yardstick_file((bench / "arch" / f"{name}.py").resolve())


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: Path = ROOT) -> Cell:
    b = benchmark(root)
    entries = [w for w in b["workloads"] if w["name"] == name]
    if not entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = entries[0]
    cfg = config_file(w["config"], root / "bench")
    arch = yardstick(cfg, root / "bench")
    return Cell(
        name=name, chips=int(w["chips"]), config=cfg,
        traffic=traffic_file(w["traffic"], root / "bench"),
        arch=arch, spec=arch.ModelSpec.from_file(cfg),
        per_layer=[m for m in b["per_layer"] if _applies(m, name)],
        end_to_end=[m for m in b["end_to_end"] if _applies(m, name)],
    )


def metric_module(name: str, bench: Path = BENCH):
    """``bench/metrics/<name>.py``: its ``read(run) -> float | None``
    and, for a kernel's metric, ``KERNELS`` (name -> trace patterns)."""
    return _load(bench / "metrics" / f"{name}.py",
                 f"bench_metric_{name.replace('.', '_').replace('-', '_')}")
