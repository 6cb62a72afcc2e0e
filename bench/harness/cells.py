"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

    BENCHMARK.json            workloads, configs, metrics
    bench/configs/<c>.json    a configuration (the ``file`` of its entry)
    bench/traffic/<t>.json    a traffic mix
    bench/cells/<w>.json      a cell's operator settings, rate and limits
    bench/metrics/<m>.py      the reader of per-layer metric ``m``
    bench/harness/arch/<a>.py a tier's architecture (the ``arch`` of its
                              entry in the configuration; ``dense_gqa``
                              without one): layout, reference, counts

A metric ``a.b`` whose quantity is split across cells is read by
``metrics/a.b.py`` if there is one, else by ``metrics/a.py``.  Adding a
cell, mix, configuration, architecture or metric adds files and entries;
no file that is there changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import List

from bench.harness import arch

ROOT = Path(__file__).resolve().parents[2]


@dataclass
class Cell:
    name: str
    root: Path
    chips: int
    config: dict        # the configuration file
    traffic: dict       # the traffic file
    settings: dict      # the cell file
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def tiers(self) -> List[dict]:
        return [self.config["fast"], self.config["expensive"]]


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, root: Path = ROOT) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(entries)}")
    w = entries[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    data = root / "bench"
    return Cell(
        name=workload, root=root, chips=int(w["chips"]),
        config=_load_json(root / configs[w["config"]]["file"]),
        traffic=_load_json(data / "traffic" / f"{w['traffic']}.json"),
        settings=_load_json(data / "cells" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def reader_path(metric: str, root: Path = ROOT) -> Path:
    d = root / "bench" / "metrics"
    own = d / f"{metric}.py"
    return own if own.exists() else d / f"{metric.split('.')[0]}.py"


def load_reader(metric: str, root: Path = ROOT):
    """The ``read(run) -> float | None`` function of a per-layer metric."""
    path = reader_path(metric, root)
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def tier_config(tier: dict, root: Path = ROOT):
    """The tier's ``ModelConfig``: the registry entry it names, with the
    file's ``registry_replace`` applied (the depth cut), registered under
    the file's ``served_name``, bound to the tier's architecture module,
    and checked against the file's published-key values that the
    module's ``HF_KEYS`` names."""
    from repro.configs.base import get_config, register

    module = arch.load(tier.get("arch", arch.DEFAULT), root)
    base = get_config(tier["registry"], tier.get("registry_variant"))
    cfg = dataclasses.replace(base, name=tier["served_name"],
                              **tier.get("registry_replace", {}))
    keys = module.HF_KEYS
    for key, want in tier["config"].items():
        if key in keys and keys[key](cfg) != want:
            raise ValueError(f"{cfg.name}: {key} is {keys[key](cfg)} "
                             f"in the program, {want} in the file")
    cfg = register(cfg)
    arch.bind(cfg, module)
    return cfg
