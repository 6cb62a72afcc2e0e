"""A whole benchmark run at smoke size on the CPU: the served window,
the end-to-end metrics, and the correctness check against the float32
reference, which a token altered where it is produced must fail."""
from __future__ import annotations

import io
import json
import time

from bench.harness import cells, main
from bench.tests import smoke_cell


def _run(tmp_path, seed=2**31 + 11):
    root = smoke_cell.make_root(tmp_path)
    cell = cells.resolve("smoke.chat", root)
    out, err = io.StringIO(), io.StringIO()
    res = main.run(cell, seed, 1.5, False, time.perf_counter(), out, err)
    return res, out.getvalue(), err.getvalue()


def test_smoke_run_is_correct(tmp_path):
    res, out, err = _run(tmp_path)
    assert json.loads(out.strip().splitlines()[-1]) == res
    assert res["correct"], err
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "latency_p50_s",
                                   "latency_p95_s"}
    assert 0 < res["metrics"]["latency_p50_s"]["value"] \
        <= res["metrics"]["latency_p95_s"]["value"]
    assert list(res)[-1] == "compared"
    assert err.strip().splitlines()[-1].startswith("compared ")


def test_altered_token_is_caught(tmp_path, monkeypatch):
    """The fault a served cell can have: a token altered where the tier
    produces it.  The served stream then no longer follows the weights."""
    from repro.serving.request import Request
    emit = Request.emit

    def altered(self, token, conf, now):
        if len(self.tokens) == 2:
            token = (token + 1) % 512
        emit(self, token, conf, now)

    monkeypatch.setattr(Request, "emit", altered)
    res, _, err = _run(tmp_path)
    assert not res["correct"], err
    assert res["compared"]["logit_gap"]["value"] \
        > res["compared"]["logit_gap"]["limit"]


def test_no_tpu_exits_nonzero_without_result(capsys):
    rc = main.main(["--workload", "stage0.chat", "--seed", "1",
                    "--seconds", "1"], time.perf_counter())
    assert rc != 0
    assert capsys.readouterr().out == ""
