"""The harness finds every cell, mix, configuration and reader by name,
and a later change adds one by adding files and entries only."""
from __future__ import annotations

import json
import re

import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import arch, cells, peaks, reference, roofline, weights
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


# an architecture unlike dense_gqa in every part the harness asks of it:
# one square matrix and one scale a layer, a tied head, its own published
# key, and counts that read a work count the harness does not know
TOY = """
import jax.numpy as jnp

from bench.harness import reference

HF_KEYS = {"hidden_size": lambda c: c.d_model,
           "toy_layers": lambda c: c.num_layers}
SEEN = set()


def layout(cfg):
    SEEN.add("layout")
    d = cfg.d_model
    out = {("embed",): ((cfg.vocab_size, d), "normal:0.02"),
           ("final_norm",): ((d,), "ones")}
    for i in range(cfg.num_layers):
        out[("toy", f"w{i}")] = ((d, d), "fan_in")
        out[("toy", f"s{i}")] = ((d,), "ones")
    return out


def logits_at(params, cfg, tokens, rows, precision="f32"):
    SEEN.add("logits_at/" + precision)
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(cfg.num_layers):
        w = reference._weight(params["toy"][f"w{i}"], precision, 0)
        x = x + jnp.tanh(x @ w) * params["toy"][f"s{i}"]
    head = reference._weight(params["embed"], precision, 1)
    return x[jnp.asarray(rows)] @ head.T


def matmul_weights(cfg):
    return cfg.num_layers * (cfg.d_model ** 2 + 1)


def pair_flops(cfg):
    return 8 * cfg.d_model * cfg.num_layers


def kv_bytes_per_position(cfg, dtype_bytes):
    return 3 * cfg.d_model * dtype_bytes


def attention_bytes(cfg, work, dtype_bytes):
    return 7 * work["tokens"]


def weight_bytes_read(cfg, work, dtype_bytes):
    return 11 * dtype_bytes + work["experts"]
"""


def test_an_added_architecture_drives_the_harness_without_edits(tmp_path):
    """A configuration whose tier names an architecture file added beside
    the others: the published-key check, the weights, the reference and
    its control, the model FLOPs and both rooflines all go through it."""
    root = smoke_cell.make_root(tmp_path)
    d = root / "bench"
    before = {p: p.read_bytes() for p in d.rglob("*") if p.is_file()}
    (d / "harness" / "arch" / "toy.py").write_text(TOY)
    fast = smoke_cell.CONFIG["fast"]
    tier = dict(fast, arch="toy", served_name="toy-bench-smoke",
                config={"toy_layers": 2, "intermediate_size": -1})
    (d / "configs" / "toy.json").write_text(json.dumps(
        dict(smoke_cell.CONFIG, name="toy", fast=tier)))
    (d / "cells" / "toy.chat.json").write_text(json.dumps(smoke_cell.CELL))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy", "source": "test",
                             "file": "bench/configs/toy.json",
                             "reduced": [], "why": "t"})
    bench["workloads"].append({"name": "toy.chat", "config": "toy",
                               "traffic": "smoke", "chips": 1, "why": "t"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.resolve("toy.chat", root)
    cfg = cells.tier_config(cell.tiers[0], root)   # dense would refuse -1
    toy = arch.of(cfg)
    assert toy is arch.load("toy", root) and toy.HF_KEYS["toy_layers"]
    assert cfg.num_layers == 2
    with pytest.raises(ValueError, match="toy_layers"):
        cells.tier_config(dict(tier, config={"toy_layers": 3}), root)
    dense = cells.tier_config(cell.tiers[1], root)
    assert arch.of(dense) is arch.load("dense_gqa", root)

    params = weights.make(cfg, 2**31 + 11, 0, jnp.float32)
    assert set(params) == {"embed", "final_norm", "toy"}
    assert set(params["toy"]) == {"w0", "w1", "s0", "s1"}
    prompt, answer = [3, 1, 4, 1, 5], [9, 2, 6]
    ref = reference.score(params, cfg, prompt, answer)
    low = reference.control_score(params, cfg, prompt, answer)
    assert toy.SEEN == {"layout", "logits_at/f32", "logits_at/fp8"}
    want = toy.logits_at(params, cfg, [3, 1, 4, 1, 5, 9, 2], [4, 5, 6])
    np.testing.assert_array_equal(ref["best"], np.max(want, axis=1))
    assert np.all(low["conf_low"] != low["conf"])    # fp8 weights differ

    dm, v, L = cfg.d_model, cfg.vocab_size, 2
    keys = sum(p + 1 for p in range(5, 20))
    assert peaks.span_flops(cfg, 5, 20, 3) == \
        2 * L * (dm * dm + 1) * 15 + 8 * dm * L * keys + 2 * dm * v * 3
    w = {"tokens": 3, "kv_read": 10, "kv_pairs": 11, "emitted": 2,
         "experts": 5}
    by_bytes = peaks.Peak(1e30, 1.0, 0.0, "test")    # seconds are bytes
    assert roofline.attention_bound_s(cfg, w, 2, by_bytes) == 7 * 3
    assert roofline.step_bound_s(cfg, w, 2, by_bytes) == \
        11 * 2 + 5 + (10 + 3) * 3 * dm * 2
    by_flops = peaks.Peak(1.0, 1e30, 0.0, "test")
    assert roofline.attention_bound_s(cfg, w, 2, by_flops) == 8 * dm * L * 11

    for name in ("nope", "../dense_gqa"):
        with pytest.raises((KeyError, ValueError)):
            cells.tier_config(dict(tier, arch=name), root)
    for p, data in before.items():
        assert p.read_bytes() == data, p
