"""Serving observability: tracer + Perfetto export, streaming gate
calibration (ECE/reliability), trace schema validation, and the
traced-vs-untraced A/B (tracing must not change token streams or host
sync counts)."""
import json
import pathlib
import sys

import numpy as np
import pytest

from repro.serving.metrics import percentile
from repro.serving.observability import (ENGINE_PID, REQUEST_PID_BASE,
                                         GateCalibration, ReliabilityBins,
                                         Tracer, length_bucket)

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "scripts"))

import check_trace  # noqa: E402


# ---------------------------------------------------------------------------
# length_bucket boundaries / percentile edge cases (satellite fixes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,label", [
    (1, "1"), (2, "2"), (3, "3-4"), (4, "3-4"),
    (5, "5-8"), (8, "5-8"), (9, "9-16"), (16, "9-16"), (17, "17-32"),
    (64, "33-64"), (65, "65-128"),
])
def test_length_bucket_boundaries(n, label):
    assert length_bucket(n) == label


def test_length_bucket_is_reexported_by_metrics():
    # docs/tests historically import it from metrics; the canonical
    # definition moved to observability — both must be the same object
    from repro.serving import metrics
    assert metrics.length_bucket is length_bucket


def test_percentile_empty_is_nan():
    assert np.isnan(percentile([], 50))
    assert percentile([3.0], 95) == 3.0


# ---------------------------------------------------------------------------
# streaming reliability bins / ECE
# ---------------------------------------------------------------------------


def closed_form_ece(confs, corrects, bins):
    """Batch ECE with the same binning as ReliabilityBins
    (bin = min(int(c*bins), bins-1), last bin closed at 1.0)."""
    confs = np.asarray(confs, np.float64)
    corrects = np.asarray(corrects, np.float64)
    idx = np.minimum((confs * bins).astype(int), bins - 1)
    err = 0.0
    for b in range(bins):
        m = idx == b
        if m.sum() == 0:
            continue
        err += (m.sum() / len(confs)) * abs(confs[m].mean()
                                            - corrects[m].mean())
    return err


def test_streaming_ece_matches_closed_form():
    rng = np.random.default_rng(7)
    confs = rng.random(500)
    corrects = rng.random(500) < confs          # roughly calibrated
    rb = ReliabilityBins(bins=10)
    for c, k in zip(confs, corrects):
        rb.record(float(c), bool(k))
    assert rb.total == 500
    assert rb.ece() == pytest.approx(
        closed_form_ece(confs, corrects, 10), abs=1e-12)


def test_reliability_bins_edges_and_empty():
    rb = ReliabilityBins(bins=4)
    assert np.isnan(rb.ece())                   # no samples yet
    rb.record(0.0, True)                        # first bin
    rb.record(1.0, True)                        # conf=1.0 -> last bin
    rb.record(0.25, False)                      # exact edge -> bin 1
    assert rb.count.tolist() == [1, 1, 0, 1]
    d = rb.diagram()
    assert d[0]["n"] == 1 and d[0]["acc"] == 1.0
    assert d[3]["n"] == 1 and d[3]["conf"] == 1.0
    assert np.isnan(d[2]["conf"])               # empty bin stays NaN


def test_perfectly_calibrated_stream_has_zero_ece():
    rb = ReliabilityBins(bins=5)
    # every sample sits at a bin center with matching realized accuracy
    for center, acc in ((0.1, 0.1), (0.5, 0.5), (0.9, 0.9)):
        for i in range(10):
            rb.record(center, i < round(acc * 10))
    assert rb.ece() == pytest.approx(0.0, abs=1e-12)


def test_gate_calibration_streams_and_summary():
    cal = GateCalibration(n_gates=2, bins=10)
    cal.record_gate(0, 0.05, True)
    cal.record_gate(0, 0.95, False)
    cal.record_gate(1, 0.55, True)
    cal.record_outcome(0, 0.05, agree=True, prompt_len=7)
    cal.record_outcome(0, 0.15, agree=False, prompt_len=20)
    assert cal.conf_hist[0].tolist()[0] == 1
    assert cal.conf_hist[0].tolist()[9] == 1
    assert cal.esc_hist[0].sum() == 1           # only the low-conf escalated
    assert cal.agreement_rate(0) == 0.5
    assert np.isnan(cal.agreement_rate(1))      # no outcomes at gate 1
    s = cal.summary()
    assert [g["gate"] for g in s] == [0, 1]
    assert s[0]["seen"] == 2 and s[0]["outcomes"] == 2
    assert set(s[0]["ece_by_prompt_bucket"]) == {"5-8", "17-32"}
    assert len(s[0]["reliability"]) == 10
    json.dumps(s, default=float)                # BENCH-serializable


# ---------------------------------------------------------------------------
# tracer: ring buffer, event structure, export schema
# ---------------------------------------------------------------------------


def test_tracer_ring_buffer_drops_oldest():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.counter("c", i)
    evs = tr.events()
    assert len(evs) == 4 and tr.dropped == 6
    assert [e["args"]["value"] for e in evs] == [6.0, 7.0, 8.0, 9.0]
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_tracer_request_lifecycle_pairs_and_export(tmp_path):
    tr = Tracer()
    tr.name_process(ENGINE_PID, "engine")
    tr.name_track(ENGINE_PID, 0, "tier0")
    tr.request_transition(7, "QUEUED", 0, prompt_tokens=12)
    tr.request_transition(7, "PREFILL", 0, shard=1)
    with tr.span("admit", tid=0, tick=3):
        pass
    tr.phase("plan", 0, tr.now_us(), width=4)
    tr.instant("gate", 0, conf=0.25)
    tr.request_done(7, 0)
    path = tmp_path / "t.json"
    n = tr.export(str(path))
    trace = json.loads(path.read_text())
    assert len(trace["traceEvents"]) == n
    assert trace["otherData"]["dropped_events"] == 0
    # schema-valid per the CI checker
    assert check_trace.validate_trace(trace) == []
    by_ph = {}
    for e in trace["traceEvents"]:
        by_ph.setdefault(e["ph"], []).append(e)
    # QUEUED and PREFILL each open ("b") and close ("e"), keyed by rid
    assert [e["name"] for e in by_ph["b"]] == ["QUEUED", "PREFILL"]
    assert all(e["id"] == 7 and e["cat"] == "request" for e in by_ph["b"])
    assert len(by_ph["e"]) == 2
    assert by_ph["b"][1]["pid"] == REQUEST_PID_BASE
    assert by_ph["b"][1]["tid"] == 1            # shard -> tid
    assert {e["name"] for e in by_ph["i"]} == {"gate", "DONE"}
    assert {e["name"] for e in by_ph["M"]} >= {"process_name",
                                               "thread_name"}


def test_check_trace_rejects_malformed_traces():
    ok = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0, "dur": 5, "pid": 0, "tid": 0},
        {"name": "b", "ph": "X", "ts": 1, "dur": 2, "pid": 0, "tid": 0},
    ]}
    assert check_trace.validate_trace(ok) == []
    cases = {
        "not an object": [1, 2],
        "missing traceEvents": {"foo": []},
        "negative dur": {"traceEvents": [
            {"name": "a", "ph": "X", "ts": 0, "dur": -1,
             "pid": 0, "tid": 0}]},
        "non-monotonic X": {"traceEvents": [
            {"name": "a", "ph": "X", "ts": 5, "dur": 1,
             "pid": 0, "tid": 0},
            {"name": "b", "ph": "X", "ts": 2, "dur": 1,
             "pid": 0, "tid": 0}]},
        "half-overlap": {"traceEvents": [
            {"name": "a", "ph": "X", "ts": 0, "dur": 4,
             "pid": 0, "tid": 0},
            {"name": "b", "ph": "X", "ts": 2, "dur": 9,
             "pid": 0, "tid": 0}]},
        "dangling b": {"traceEvents": [
            {"name": "S", "ph": "b", "cat": "request", "id": 1,
             "ts": 0, "pid": 0, "tid": 0}]},
        "e without b": {"traceEvents": [
            {"name": "S", "ph": "e", "cat": "request", "id": 1,
             "ts": 0, "pid": 0, "tid": 0}]},
        "counter without numeric value": {"traceEvents": [
            {"name": "c", "ph": "C", "ts": 0, "pid": 0, "tid": 0,
             "args": {"value": "high"}}]},
        "missing ts": {"traceEvents": [
            {"name": "a", "ph": "i", "pid": 0, "tid": 0}]},
    }
    for label, trace in cases.items():
        assert check_trace.validate_trace(trace), label


# ---------------------------------------------------------------------------
# engine integration: traced run == untraced run, spans present,
# escalation-outcome calibration, tick durations, snapshots
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cfg():
    from repro.configs import get_config
    return get_config("gemma3-1b", "smoke")


@pytest.fixture(scope="module")
def params(cfg):
    import jax
    import jax.numpy as jnp
    from repro.models import init_params
    return init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


def _engine(cfg, params, tracer=None, deltas=(0.5,)):
    """Two tiers sharing params: escalated streams agree exactly, so
    the escalation-outcome proxy must report agreement 1.0."""
    from repro.serving import CascadeEngine, TierSpec
    from repro.serving.engine import VirtualClock
    return CascadeEngine(
        [TierSpec("fast", cfg, params), TierSpec("exp", cfg, params)],
        slots=3, prompt_len=16, gen_len=4, deltas=list(deltas),
        kv_block_size=4, prefill_chunk=5, clock=VirtualClock(),
        tracer=tracer)


def _submit_all(eng, cfg, n=6):
    rng = np.random.default_rng(0)
    for i in range(n):
        p = rng.integers(0, cfg.vocab_size,
                         size=int(rng.integers(1, 17))).astype(np.int32)
        eng.submit(p, arrival_time=float(i // 2))


@pytest.fixture(scope="module")
def traced_run(cfg, params, tmp_path_factory):
    """The traced run, and what each ragged launch staged: the runtime,
    and per live row its flat positions and its request's prompt length."""
    from repro.serving.engine import _TierRuntime
    stage, staged = _TierRuntime.stage_flat, []

    def spy(rt, flat_tokens, flat_pos, qlen, qstart, *rest):
        rows, o = [], 0
        for s, n in enumerate(np.asarray(qlen).tolist()):
            if n:
                rows.append((np.asarray(flat_pos)[0, o:o + n].tolist(),
                             rt.slot_req[s].prompt_tokens))
                o += n
        staged.append((rt, rows))
        return stage(rt, flat_tokens, flat_pos, qlen, qstart, *rest)

    tr = Tracer()
    eng = _engine(cfg, params, tracer=tr)
    _submit_all(eng, cfg)
    snaps = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(_TierRuntime, "stage_flat", spy)
        summary = eng.run(metrics_interval=3.0, on_snapshot=snaps.append)
    path = tmp_path_factory.mktemp("trace") / "trace.json"
    tr.export(str(path))
    return eng, summary, tr, snaps, path, staged


def test_traced_run_matches_untraced(cfg, params, traced_run):
    eng_t, summary_t, *_ = traced_run
    eng = _engine(cfg, params, tracer=None)
    _submit_all(eng, cfg)
    summary = eng.run()
    # tracing is observational: identical token streams, launches, and
    # (the big one) host sync counts
    assert [r.tokens for r in eng.requests] \
        == [r.tokens for r in eng_t.requests]
    assert summary["launches"] == summary_t["launches"]
    assert summary["host_syncs"] == summary_t["host_syncs"]
    assert summary["host_syncs_per_tick"] == summary_t["host_syncs_per_tick"]
    assert summary["steps"] == summary_t["steps"]


def test_traced_run_emits_schema_valid_spans(traced_run):
    eng, summary, tr, _, path, _ = traced_run
    trace = json.loads(path.read_text())
    assert check_trace.validate_trace(trace) == []
    evs = trace["traceEvents"]
    phases = {e["name"] for e in evs if e["ph"] == "X"}
    assert {"tick", "admit", "plan", "launch", "put",
            "device_get", "finish"} <= phases
    states = {e["name"] for e in evs if e["ph"] == "b"}
    assert {"QUEUED", "PREFILL", "DECODE", "ESCALATED"} <= states
    dones = [e for e in evs if e["ph"] == "i" and e["name"] == "DONE"]
    assert len(dones) == summary["completed"]
    # every tick span exists once per engine step
    ticks = [e for e in evs if e["ph"] == "X" and e["name"] == "tick"]
    assert len(ticks) == summary["steps"]
    # counter tracks sample queue depth / live rows
    counters = {e["name"] for e in evs if e["ph"] == "C"}
    assert any(n.startswith("queue depth/") for n in counters)
    assert any(n.startswith("live rows/") for n in counters)


def test_ragged_launches_carry_their_work(traced_run):
    """Each ragged ``launch`` span's work counts, recomputed from the
    positions its live rows staged: tokens, keys read (each row's
    context once), query-key pairs (each token's causal context), and
    the rows whose logits are used (those reaching past the prompt)."""
    eng, _, tr, _, _, staged = traced_run
    launches = [e for e in tr.events()
                if e["ph"] == "X" and e["name"] == "launch"]
    assert launches and {e["args"]["kind"] for e in launches} == {"ragged"}
    for t, rt in enumerate(eng.runtimes):
        got = [e["args"] for e in launches if e["tid"] == t]
        want = [rows for r, rows in staged if r is rt and rows]
        assert len(got) == len(want) > 0
        for args, rows in zip(got, want):
            assert args["tokens"] == sum(len(p) for p, _ in rows)
            assert args["kv_read"] == sum(p[-1] + 1 for p, _ in rows)
            assert args["kv_pairs"] == sum(q + 1 for p, _ in rows for q in p)
            assert args["emitted"] == sum(p[-1] + 1 >= n for p, n in rows)


def test_each_launch_holds_one_put(traced_run):
    _, _, tr, _, _, _ = traced_run
    spans = [e for e in tr.events() if e["ph"] == "X"]
    launches = [e for e in spans if e["name"] == "launch"]
    puts = [e for e in spans if e["name"] == "put"]
    assert launches and len(puts) == len(launches)
    for ln in launches:
        inside = [p for p in puts if p["tid"] == ln["tid"]
                  and ln["ts"] <= p["ts"]
                  and p["ts"] + p["dur"] <= ln["ts"] + ln["dur"]]
        assert len(inside) == 1, ln


def test_escalation_outcome_calibration(traced_run):
    _, summary, *_ = traced_run
    cal = summary["gate_calibration"]
    assert len(cal) == 1
    g = cal[0]
    # both tiers share params -> escalated token streams always agree
    assert g["outcomes"] > 0
    assert g["agreement_rate"] == 1.0
    # confidences are tiny (random params over a big vocab) and realized
    # "accuracy" is 1.0, so the proxy-ECE sits near 1 - mean_conf
    assert 0.9 < g["ece"] <= 1.0
    assert sum(g["conf_hist"]) == g["seen"] > 0
    assert g["ece_by_prompt_bucket"]            # bucketed slice populated


def test_no_escalation_means_no_outcomes(cfg, params):
    eng = _engine(cfg, params, deltas=(0.0,))   # conf > 0 -> never escalate
    _submit_all(eng, cfg, n=3)
    summary = eng.run()
    g = summary["gate_calibration"][0]
    assert g["outcomes"] == 0
    assert np.isnan(g["agreement_rate"]) and np.isnan(g["ece"])
    assert g["seen"] > 0                        # decisions still streamed


def test_tick_durations_under_virtual_clock(traced_run):
    eng, summary, *_ = traced_run
    # VirtualClock advances exactly 1.0 per engine step
    assert summary["tick_duration_p50"] == 1.0
    assert summary["tick_duration_max"] == 1.0
    assert summary["tick_duration_hist"] == {"1e0": summary["steps"] - 1}
    assert len(eng.metrics.tick_durations) == summary["steps"] - 1


def test_metrics_interval_snapshots(traced_run):
    _, summary, _, snaps, *_ = traced_run
    assert snaps, "run(metrics_interval=...) emitted no snapshots"
    assert all(s["t"] <= summary["steps"] + 1 for s in snaps)
    ts = [s["t"] for s in snaps]
    assert ts == sorted(ts)
    last = snaps[-1]
    assert {"completed", "escalation_rates", "gate_ece",
            "gate_agreement", "tick_duration_p50"} <= set(last)
