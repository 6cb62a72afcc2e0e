"""The correctness check's control at smoke size: the engine's served
tokens and confidences agree with the float32 reference, and the same
sample scored with the fp8 reference in the program's place fails."""
from __future__ import annotations

import io
import time

from bench.harness import cells, compare, main
from bench.tests import smoke_cell


def test_program_agrees_and_fp8_control_fails(tmp_path):
    cell = cells.resolve("smoke.chat", smoke_cell.make_root(tmp_path))
    keep = {}
    res = main.run(cell, 5, 1.5, False, time.perf_counter(), io.StringIO(),
                   io.StringIO(), keep=keep)
    prog = {k: res["compared"][k]["value"] for k in compare.NUMBERS}
    assert res["correct"], prog
    assert any(r.tier > 0 for r in keep["picked"])      # both tiers scored
    low = compare.control_readings(keep["picked"], keep["params"],
                                   keep["cfgs"])
    assert not compare.judge(low, cell.settings["limits"]), low
    for k in compare.NUMBERS:
        assert low[k] > 10 * prog[k], (k, low[k], prog[k])
