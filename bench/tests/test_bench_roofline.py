"""The readers of the launches' work: the roofline shares of
``ragged_attention`` and of the whole step, and the puts per tick, on a
hand-made run with hand-computed answers; and the clock pairing that
puts the Tracer's phases on the recorded chip trace's clock."""
from __future__ import annotations

import gzip
import json
from collections import defaultdict
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.harness import cells, peaks, roofline, tracered
from repro.configs.base import Attn, Dense, Layer, ModelConfig

FIXTURES = Path(__file__).parent / "fixtures"


def _cfg(window=None, tied=True):
    """3 layers, d 4, vocab 10 (tied), 2 heads of 2 over 1 KV head,
    SwiGLU 8: 144 matrix weights a layer (48 attention + 96 feed-forward),
    500 weights read whole, 24 bytes of K and V a position in bfloat16."""
    return ModelConfig(name=f"tiny-w{window}", family="dense", d_model=4,
                       vocab_size=10, num_heads=2, num_kv_heads=1,
                       head_dim=2, num_periods=3, tie_embeddings=tied,
                       period=(Layer(Attn(window=window), Dense(d_ff=8)),))


# a prefill row at positions 0-1 finishing its prompt, and a decode row at
# position 7: keys read 2 + 8, query-key pairs (1 + 2) + 8
WORK = {"tokens": 3, "kv_read": 10, "kv_pairs": 11, "emitted": 2}
# attention: 10 * 24 bytes of K, V + 3 tokens * 3 layers * 2 heads * 2 dims
# * 2 (q, o) * 2 bytes = 384 bytes; 4 * 2 * 2 * 11 * 3 = 528 FLOPs.
# step: 1000 bytes of weights + (10 + 3) * 24 of KV = 1312 bytes;
# 2 * 144 * 3 layers * 3 tokens + 528 + 2 * 4 * 10 * 2 emitted = 3280 FLOPs.
# Untied, the embedding adds its rows of the 3 tokens: 3 * 4 * 2 = 24 bytes
BANDWIDTH_BOUND = peaks.Peak(1000.0, 100.0, 0.0, "test")
FLOPS_BOUND = peaks.Peak(100.0, 1000.0, 0.0, "test")


def _run(cfg, args=WORK, phases=()):
    launches = [{"name": "launch", "ph": "X", "ts": 10.0 * t, "dur": 5.0,
                 "tid": t, "args": dict(args, tick=1, kind="ragged")}
                for t in (0, 1)]
    return SimpleNamespace(
        phases=launches + list(phases), cfgs=[cfg, cfg],
        cell=SimpleNamespace(config={"dtype": "bfloat16"}),
        trace=tracered.Summary(window_s=100.0, chips=1, busy_s=52.48,
                               kernel_s={"ragged_attention": 38.4}),
        peak=BANDWIDTH_BOUND)


@pytest.mark.parametrize("bound,peak,tied,seconds", [
    (roofline.attention_bound_s, BANDWIDTH_BOUND, True, 3.84),
    (roofline.attention_bound_s, FLOPS_BOUND, True, 5.28),
    (roofline.step_bound_s, BANDWIDTH_BOUND, True, 13.12),
    (roofline.step_bound_s, FLOPS_BOUND, True, 32.8),
    (roofline.step_bound_s, BANDWIDTH_BOUND, False, 13.36),
])
def test_a_launch_is_bound_by_the_larger_of_bytes_and_flops(bound, peak,
                                                            tied, seconds):
    assert bound(_cfg(tied=tied), WORK, 2, peak) == pytest.approx(seconds)


@pytest.mark.parametrize("tied", [True, False])
def test_weights_are_counted_once(tied):
    """A tied head once; an untied head whole, its embedding by rows."""
    assert roofline.weight_bytes(_cfg(tied=tied), 2) == 1000


@pytest.mark.parametrize("metric,value", [
    ("ragged_attention_roofline.tail", 20.0),   # 2 * 3.84 s over 38.4 s
    ("step_roofline.tail", 50.0),               # 2 * 13.12 s over 52.48 s
])
def test_roofline_shares(metric, value):
    assert cells.load_reader(metric)(_run(_cfg())) == pytest.approx(value)


@pytest.mark.parametrize("metric", ["ragged_attention_roofline.tail",
                                    "step_roofline.tail"])
def test_a_windowed_tier_reads_nothing(metric, capsys):
    assert cells.load_reader(metric)(_run(_cfg(window=4))) is None
    assert "sliding window" in capsys.readouterr().err


@pytest.mark.parametrize("metric", ["ragged_attention_roofline.tail",
                                    "step_roofline.tail",
                                    "put_ms_per_tick.tail"])
def test_a_program_that_does_not_count_reads_nothing(metric):
    """The launches of a program without the work counts or the ``put``
    spans: the readers find nothing and do not raise."""
    assert cells.load_reader(metric)(_run(_cfg(), args={})) is None


def test_puts_per_tick():
    phases = [{"name": "tick", "ph": "X", "ts": 0.0, "dur": 20.0, "tid": 2},
              {"name": "tick", "ph": "X", "ts": 20.0, "dur": 20.0, "tid": 2},
              {"name": "put", "ph": "X", "ts": 1.0, "dur": 300.0, "tid": 0},
              {"name": "put", "ph": "X", "ts": 21.0, "dur": 500.0, "tid": 1}]
    read = cells.load_reader("put_ms_per_tick.tail")
    assert read(_run(_cfg(), phases=phases)) == pytest.approx(0.4)


def test_launch_annotations_pair_with_launch_phases_on_the_recorded_trace():
    """Each ``run_ragged/<tier>`` annotation of the chip trace opens with
    the Tracer's ``launch`` of the same launch: the differences of the
    pairs spread by under 10 us, so one offset puts every phase on the
    trace's clock."""
    from jax.profiler import ProfileData
    meta = json.loads((FIXTURES / "stage0_chat.phases.json").read_text())
    with gzip.open(FIXTURES / "stage0_chat.xplane.pb.gz", "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    host = defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("run_ragged/"):
                        host[ev.name.split("/", 1)[1]].append(ev.start_ns)
    diffs = []
    for t, tier in enumerate(meta["tiers"]):
        ann = sorted(host[tier])
        lau = sorted(e["ts"] * 1e3 for e in meta["phases"]
                     if e["name"] == "launch" and e["tid"] == t)
        assert len(ann) == len(lau) > 0
        diffs += [a - b for a, b in zip(ann, lau)]
    assert max(diffs) - min(diffs) < 10e3
    offset = tracered.clock_offset_ns(host, meta["phases"], meta["tiers"])
    assert min(diffs) <= offset <= max(diffs)
