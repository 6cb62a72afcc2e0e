"""The harness finds every cell, mix, configuration and reader by name,
and a later change adds one by adding files and entries only."""
from __future__ import annotations

import json
import re

from bench.harness import cells
from bench.tests import smoke_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_of_the_benchmark_resolves():
    bench = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    e2e = {m["name"] for m in bench["end_to_end"]}
    for c in bench["configs"]:        # one `reduced`, kept in two places
        assert c["reduced"] == json.loads(
            (cells.ROOT / c["file"]).read_text())["reduced"], c["name"]
    for w in bench["workloads"]:
        cell = cells.resolve(w["name"])
        assert {"setup_s"} < {m["name"] for m in cell.end_to_end}
        assert cell.per_layer
        for m in cell.per_layer:
            assert cells.reader_path(m["name"]).exists(), m["name"]
            assert m["moves"] in {x["name"] for x in cell.end_to_end}
        for tier in cell.tiers:
            cfg = cells.tier_config(tier)
            assert cfg.num_layers == tier["config"]["num_hidden_layers"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_added_files_and_entries_resolve_without_edits(tmp_path):
    root = smoke_cell.make_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    d = root / "bench"
    (d / "traffic" / "dummy.json").write_text(json.dumps(
        dict(smoke_cell.TRAFFIC, answer_tokens=3)))
    (d / "cells" / "smoke.dummy.json").write_text(
        json.dumps(smoke_cell.CELL))
    (d / "metrics" / "dummy_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "smoke.dummy",
                               "config": "smoke-cascade",
                               "traffic": "dummy", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "dummy_metric.x", "unit": "%",
                               "better": "lower", "source": "program_counter",
                               "layer": "scheduler", "moves": "setup_s",
                               "workloads": ["smoke.dummy"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.resolve("smoke.dummy", root)
    assert cell.traffic["answer_tokens"] == 3
    assert [m["name"] for m in cell.per_layer] == ["dummy_metric.x"]
    assert cells.load_reader("dummy_metric.x", root)(None) == 42.0
    assert cells.resolve("smoke.chat", root).traffic == smoke_cell.TRAFFIC
    for p, data in before.items():
        assert p.read_bytes() == data, p
