"""The traffic generator gives every seed the same work in a balanced
order."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench.harness import cells, traffic

MIXES = sorted(p.stem for p in (cells.ROOT / "bench" / "traffic").glob("*.json"))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_lengths_and_gaps(mix):
    spec = json.loads((cells.ROOT / "bench" / "traffic" / f"{mix}.json")
                      .read_text())
    runs = [traffic.generate(spec, 1.5, 40.0, 1000, seed)
            for seed in (3, 2**31 + 11)]
    lengths = [sorted(len(q.prompt) for q in r) for r in runs]
    gaps = [sorted(np.diff([q.arrival for q in r]).round(9)) for r in runs]
    assert lengths[0] == lengths[1] and gaps[0] == gaps[1]
    assert [len(q.prompt) for q in runs[0]] != [len(q.prompt) for q in runs[1]]
    p = spec["prompt"]
    assert all(p["min"] <= len(q.prompt) <= p["max"] for q in runs[0])


def test_each_round_takes_one_of_each_stratum():
    n, k = 43, traffic.STRATA
    ascending = np.arange(n)
    stratum = {v: max(i for i in range(k) if (i * n) // k <= v)
               for v in range(n)}
    order = traffic.balanced_order(ascending, np.random.default_rng(5))
    assert sorted(order.tolist()) == ascending.tolist()
    for r in range(n // k):
        got = sorted(stratum[int(v)] for v in order[r * k:(r + 1) * k])
        assert got == list(range(k))
