"""A benchmark root at smoke size, built in a temporary directory: the
real harness and readers, with a configuration, a traffic mix and a cell
small enough for the CPU."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

CONFIG = {
    "name": "smoke-cascade", "source": "test", "dtype": "float32",
    "gate": {"escalation_budget": 0.5}, "tier_mesh": None, "reduced": [],
    "fast": {"registry": "phi4-mini-3.8b", "registry_variant": "smoke",
             "served_name": "phi4-bench-smoke", "config": {}},
    "expensive": {"registry": "starcoder2-7b", "registry_variant": "smoke",
                  "served_name": "sc2-bench-smoke", "config": {}},
}
TRAFFIC = {"arrival": {"process": "poisson"},
           "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                      "min": 4, "max": 48},
           "answer_tokens": 6}
CELL = {"judged_on": "tail", "rate_per_s": 12.0, "slots": 4,
        "flat_buckets": [16, 64, 192], "prewindow_s": 0.5, "drain_s": 120,
        "sample_requests": 3, "trace_s": 1.0,
        "limits": {"logit_gap": 1e-3, "conf_rel_err": 1e-3}}


def make_root(tmp: Path, cell: dict = CELL) -> Path:
    """A checkout-shaped directory: ``BENCHMARK.json`` naming one smoke
    cell, the real metric readers and architecture files, and the smoke
    data files."""
    root = tmp / "root"
    for d in ("configs", "traffic", "cells"):
        (root / "bench" / d).mkdir(parents=True)
    shutil.copytree(BENCH / "metrics", root / "bench" / "metrics")
    shutil.copytree(BENCH / "harness" / "arch", root / "bench" / "harness"
                    / "arch", ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench/configs/smoke-cascade.json").write_text(json.dumps(CONFIG))
    (root / "bench/traffic/smoke.json").write_text(json.dumps(TRAFFIC))
    (root / "bench/cells/smoke.chat.json").write_text(json.dumps(cell))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "smoke-cascade", "source": "test",
                         "file": "bench/configs/smoke-cascade.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": "smoke.chat", "config": "smoke-cascade",
                           "traffic": "smoke", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["smoke.chat"] if m["name"] in (
                "latency_p50_s", "latency_p95_s") or m["name"].endswith(
                ".tail") or m["name"] == "queue_wait_p95_s" else []
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
