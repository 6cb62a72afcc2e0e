"""The trace reduction on a device trace recorded on one TPU v5e chip: a
short traced window of ``stage0.chat`` (``bench/run.py --trace 1
--keep-trace``), with the Tracer phases of the same window.  It pins the
profiler's layout that the reduction reads: the chip's op line, the two
kernels' names, and the launch annotations that put the host phases on
the trace's clock."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench.harness import tracered

FIXTURES = Path(__file__).parent / "fixtures"


@pytest.fixture(scope="module")
def recorded():
    meta = json.loads((FIXTURES / "stage0_chat.phases.json").read_text())
    summary = tracered.reduce(str(FIXTURES / "stage0_chat.xplane.pb.gz"),
                              meta["devices"], meta["phases"], meta["tiers"],
                              meta["window_s"])
    return meta, summary


def test_the_chip_and_its_busy_time_are_found(recorded):
    meta, s = recorded
    assert s.chips == len(meta["devices"]) == 1
    assert 0 < s.busy_s < s.window_s
    # the body ops of a loop are not counted twice: the ops left after
    # the control-flow containers tile the busy time
    assert not set(s.ops_s) & set(tracered.CONTAINERS)
    assert sum(s.ops_s.values()) == pytest.approx(s.busy_s, rel=0.02)


def test_both_kernels_are_found_by_name(recorded):
    _, s = recorded
    assert set(s.kernel_s) == set(tracered.KERNELS)
    assert all(0 < t < s.busy_s for t in s.kernel_s.values())


def test_idle_gaps_are_put_on_host_phases(recorded):
    meta, s = recorded
    assert s.idle_by_phase
    idle = s.window_s - s.busy_s
    assert sum(s.idle_by_phase.values()) == pytest.approx(idle, rel=0.05)
    tiers = {lab.split("/")[1] for lab in s.idle_by_phase if "/" in lab}
    assert tiers and tiers <= set(meta["tiers"])


def test_breakdown_is_at_most_ten_of_each(recorded):
    _, s = recorded
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    times = [t for _, t in b["device_ops"]]
    assert times == sorted(times, reverse=True)
