"""The reduction from a device trace to busy, idle, kernel and gap
numbers: its interval arithmetic and host-phase attribution on
hand-made intervals."""
from __future__ import annotations

import numpy as np

from bench.harness import tracered


def test_union_and_gaps():
    iv = np.array([[5, 7], [0, 2], [1, 3], [7, 8], [10, 12]], float)
    assert tracered.union_length(iv) == 3 + 3 + 2
    assert tracered.gaps(iv, 0, 14).tolist() == [[3, 5], [8, 10], [12, 14]]
    assert tracered.gaps(iv[:0], 0, 4).tolist() == [[0, 4]]


def test_gaps_are_attributed_to_the_host_phase():
    phases = [
        {"name": "tick", "ph": "X", "ts": 0.0, "dur": 100.0, "tid": 2},
        {"name": "plan", "ph": "X", "ts": 10.0, "dur": 20.0, "tid": 0},
        {"name": "device_get", "ph": "X", "ts": 40.0, "dur": 30.0, "tid": 1},
    ]
    # tracer time t us is trace time 1000 t + 5000 ns
    mids = np.array([15.0, 50.0, 90.0, 150.0]) * 1e3 + 5000
    assert tracered.attribute(mids, phases, 5000.0, ["a", "b"]) == \
        ["plan/a", "device_get/b", "tick", "between ticks"]


def test_clock_offset_pairs_launches_in_order():
    phases = [{"name": "launch", "ts": t, "dur": 1.0, "tid": tid}
              for t, tid in ((10.0, 0), (20.0, 1), (30.0, 0))]
    host = {"a": [10_500.0, 30_500.0], "b": [20_500.0]}
    assert tracered.clock_offset_ns(host, phases, ["a", "b"]) == 500.0
