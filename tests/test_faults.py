"""Overload and failure semantics: preemption, load shedding, launch
retry, and the deterministic fault-injection harness.

The chaos oracles (docs/serving.md "Overload and failure semantics"):

  * **No deadlock** — every faulted run drains within a bounded number
    of steps.
  * **Bit-identity** — per-(request, tier) token streams are
    deterministic functions of (prompt, tier params) under greedy
    decode, so surviving requests must produce streams identical to a
    fault-free run: preemption replays, retry relaunches, pool
    shrinkage, and escalation storms (which change *routing*, never a
    tier's tokens) all leave them untouched.
  * **Conservation** — submitted == completed + shed + failed at drain.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.serving import (BlockAllocator, CascadeEngine, FaultPlan,
                           Request, RequestState, SlotAllocator, TierSpec,
                           TransientError)
from repro.serving.engine import VirtualClock
from repro.serving.faults import Shrink, Storm
from repro.serving.request import TERMINAL_STATES
from repro.serving.scheduler import CascadeScheduler, GateSpec
from repro.serving.slots import TierSlotPool


# ---------------------------------------------------------------------------
# FaultPlan: parsing and determinism
# ---------------------------------------------------------------------------


def test_fault_plan_parse_full_grammar():
    p = FaultPlan.parse("seed=7,shrink=5:0:8:40,storm=10-14:1,"
                        "launch=0.05:2,launchat=3:1:4,slow=0.1:0.01")
    assert p.seed == 7
    assert p.shrinks == (Shrink(5, 0, 8, 40),)
    assert p.storms == (Storm(10, 14, 1),)
    assert p.launch_fail_prob == 0.05 and p.launch_fail_attempts == 2
    assert p.fail_launches == {(3, 1): 4}
    assert p.slow_tick_prob == 0.1 and p.slow_tick_seconds == 0.01
    # defaults: restore never, gate 0, one failing attempt
    p2 = FaultPlan.parse("shrink=1:0:4,storm=2-3,launchat=5:0")
    assert p2.shrinks[0].restore_tick is None
    assert p2.storms[0].gate == 0
    assert p2.fail_launches == {(5, 0): 1}


@pytest.mark.parametrize("bad", [
    "frobnicate=1", "shrink=1:2", "storm=5", "slow=0.5", "launch",
])
def test_fault_plan_parse_rejects(bad):
    with pytest.raises(ValueError):
        FaultPlan.parse(bad)


def test_fault_plan_draws_are_pure_and_order_independent():
    a, b = FaultPlan(seed=3), FaultPlan(seed=3)
    keys = [(1, 0, 1), (9, 1, 5), (1, 0, 1), (2, 0, 3)]
    # same key -> same draw regardless of what was drawn before
    assert [a._draw(*k) for k in keys] \
        == [b._draw(*k) for k in reversed(keys)][::-1]
    assert a._draw(1, 0, 1) == a._draw(1, 0, 1)
    assert FaultPlan(seed=4)._draw(1, 0, 1) != a._draw(1, 0, 1)


def test_fault_plan_pre_launch_targets_and_recovers():
    p = FaultPlan(fail_launches={(2, 0): 2})
    with pytest.raises(TransientError):
        p.pre_launch(2, 0, "run_mixed", 0)
    with pytest.raises(TransientError):
        p.pre_launch(2, 0, "run_mixed", 1)
    p.pre_launch(2, 0, "run_mixed", 2)      # attempts exhausted: passes
    p.pre_launch(3, 0, "run_mixed", 0)      # other ticks untouched
    assert [e[1] for e in p.log] == ["launch_fault", "launch_fault"]


def test_fault_plan_storm_window():
    p = FaultPlan(storms=(Storm(5, 8, gate=1),))
    assert p.force_escalation(4, 1) is None
    assert p.force_escalation(5, 1) is True
    assert p.force_escalation(7, 1) is True
    assert p.force_escalation(8, 1) is None         # end-exclusive
    assert p.force_escalation(6, 0) is None         # other gate


# ---------------------------------------------------------------------------
# satellite: double-free / double-release guards
# ---------------------------------------------------------------------------


def test_slot_allocator_double_free_raises():
    a = SlotAllocator(2)
    s = a.alloc()
    a.free(s)
    with pytest.raises(ValueError, match="double free"):
        a.free(s)
    with pytest.raises(ValueError, match="double free"):
        a.free(1 - s)                       # never allocated


def test_block_allocator_double_free_raises():
    a = BlockAllocator(4)
    b = a.alloc()
    a.free(b)
    with pytest.raises(ValueError, match="double free"):
        a.free(b)
    with pytest.raises(ValueError, match="double free"):
        a.free(0)                           # the null block


def _pool():
    from repro.configs import get_config
    cfg = get_config("gemma3-1b", "smoke")
    return TierSlotPool(cfg, capacity=4, max_seq=16, block_size=4,
                        num_blocks=13)


def test_tier_slot_pool_double_release_raises():
    pool = _pool()
    pool.bind(0, 8)
    pool.release(0)
    with pytest.raises(ValueError, match="double release"):
        pool.release(0)
    with pytest.raises(ValueError, match="double release"):
        pool.release(1)                     # never bound


# ---------------------------------------------------------------------------
# fault-injected pool shrinkage: deadlock-safety caps
# ---------------------------------------------------------------------------


def test_shrink_caps_preserve_floor_and_oldest_reserve():
    pool = _pool()                          # 12 usable blocks, 4/row
    pool.bind(0, 4, row_tokens=16)          # oldest: holds 1, demands 3 more
    # floor cap: usable - pages_per_row = 12 - 4 = 8; reserve cap:
    # free (11) - oldest_worst (3) = 8 -> a huge request takes only 8
    assert pool.shrink(100) == 8
    assert pool.blocks.reserved_in(0) == 8
    # the oldest row can still grow to its full demand
    assert pool.ensure_blocks(0, 15)
    assert pool.unshrink() == 8
    assert pool.shrink(2) == 2              # partial shrink under the cap
    pool.unshrink()


def test_shrink_keeps_one_full_request_admissible():
    pool = _pool()
    pool.shrink(100)                        # empty pool: floor cap binds
    assert pool.blocks.num_free >= pool.pages_per_row
    assert pool.can_admit(16)
    pool.unshrink()


# ---------------------------------------------------------------------------
# request lifecycle: new states
# ---------------------------------------------------------------------------


def test_request_overload_transitions():
    r = Request(rid=0, prompt=np.zeros(4, np.int32), gen_len=2,
                arrival_time=0.0)
    r.admit(0, 0, 1.0)
    r.preempt(2.0)
    assert r.state is RequestState.PREEMPTED and r.preemptions == 1
    assert r.slot is None
    r.admit(0, 1, 3.0)                      # replay resets partial work
    assert r.tokens == [] and r.token_conf == []
    r.start_decode(4.0)
    r.fail(5.0)
    assert r.state in TERMINAL_STATES
    with pytest.raises(ValueError):
        r.admit(0, 0, 6.0)                  # terminal states stay terminal

    q = Request(rid=1, prompt=np.zeros(4, np.int32), gen_len=2,
                arrival_time=0.0, deadline=1.0)
    q.shed(2.0)
    assert q.state is RequestState.SHED
    with pytest.raises(ValueError):
        q.shed(3.0)


# ---------------------------------------------------------------------------
# scheduler: shedding pass and preempted re-queue
# ---------------------------------------------------------------------------


def _sched_req(rid, arrival=0.0, deadline=None):
    return Request(rid=rid, prompt=np.zeros(4, np.int32), gen_len=2,
                   arrival_time=arrival, deadline=deadline)


def test_scheduler_shed_expired_and_unmeetable():
    sched = CascadeScheduler([2, 2], [GateSpec(delta=0.5)])
    keep = _sched_req(0, deadline=None)           # no deadline: never shed
    expired = _sched_req(1, deadline=5.0)
    tight = _sched_req(2, deadline=12.0)          # meetable without floor
    for r in (keep, expired, tight):
        sched.submit(r)
    shed = sched.shed(0, now=10.0, floor=None)
    assert [r.rid for r in shed] == [1]
    assert [r.rid for r in sched.queues[0]] == [0, 2]   # order preserved
    # with a service-time floor, provably-unmeetable deadlines shed too
    shed = sched.shed(0, now=10.0, floor=lambda r: 5.0)
    assert [r.rid for r in shed] == [2]


def test_scheduler_requeue_puts_preempted_at_head():
    sched = CascadeScheduler([2, 2], [GateSpec(delta=0.5)])
    a, b = _sched_req(0), _sched_req(1)
    sched.submit(a)
    sched.submit(b)
    victim = _sched_req(2)
    sched.requeue(victim, 0)
    assert [r.rid for r in sched.queues[0]] == [2, 0, 1]


# ---------------------------------------------------------------------------
# engine chaos suite (smoke models)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_parts():
    from repro.configs import get_config
    from repro.models import init_params
    cfg = get_config("gemma3-1b", "smoke")
    p0 = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    p1 = init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    return cfg, p0, p1


def _build(parts, tiers=1, **kw):
    cfg, p0, p1 = parts
    specs = [TierSpec("fast", cfg, p0)]
    if tiers == 2:
        specs.append(TierSpec("exp", cfg, p1))
        kw.setdefault("deltas", [0.5])
    kw.setdefault("retry_backoff", 0.0)
    kw.setdefault("slots", 2)
    kw.setdefault("prompt_len", 16)
    kw.setdefault("gen_len", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("kv_block_size", 4)
    return CascadeEngine(specs, clock=VirtualClock(), **kw)


def _prompts(cfg, n=6, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
            for _ in range(n)]


def _drain(eng, prompts, deadline=None, max_steps=500):
    for p in prompts:
        eng.submit(p, arrival_time=0.0, deadline=deadline)
    s = eng.run(max_steps=max_steps)
    assert all(r.state in TERMINAL_STATES for r in eng.requests)
    assert s["conservation"]["ok"], s["conservation"]
    return s


def _streams(eng):
    return {r.rid: list(r.tokens) for r in eng.requests}


@pytest.fixture(scope="module")
def ref_streams(tiny_parts):
    """Fault-free single-tier reference streams (the chaos oracle)."""
    eng = _build(tiny_parts)
    _drain(eng, _prompts(tiny_parts[0]))
    return _streams(eng)


@pytest.mark.parametrize("policy", ["youngest", "fewest-tokens"])
def test_preemption_replays_bit_identical(tiny_parts, ref_streams, policy):
    # 4 slots into a 14-block arena (pages_per_row=5): over-subscribed,
    # rows stall mid-decode -> the policy evicts and replays instead
    eng = _build(tiny_parts, slots=4, kv_blocks=14,
                 preemption_policy=policy)
    s = _drain(eng, _prompts(tiny_parts[0]))
    assert s["preemptions"] > 0 and s["replayed_tokens"] > 0
    assert s["completed"] == 6 and s["failed"] == 0
    assert _streams(eng) == ref_streams
    assert all(r.preemptions == 0 or r.state is RequestState.DONE
               for r in eng.requests)


def test_preemption_requires_chunked_paged_path(tiny_parts):
    with pytest.raises(ValueError, match="preemption"):
        _build(tiny_parts, use_paged_kv=False,
               preemption_policy="youngest")
    with pytest.raises(ValueError, match="preemption_policy"):
        _build(tiny_parts, preemption_policy="oldest")


def test_deadline_shedding_conserves(tiny_parts):
    # 2 slots, 6 requests, deadlines only the first waves can meet
    eng = _build(tiny_parts)
    s = _drain(eng, _prompts(tiny_parts[0]), deadline=6.0)
    assert s["shed"] > 0 and s["completed"] > 0
    assert s["shed"] + s["completed"] == s["submitted"] == 6
    assert 0.0 < s["shed_rate"] < 1.0
    shed = [r for r in eng.requests if r.state is RequestState.SHED]
    assert all(r.deadline is not None for r in shed)
    # no-deadline submissions are never shed even under the same load
    eng = _build(tiny_parts)
    s = _drain(eng, _prompts(tiny_parts[0]))
    assert s["shed"] == 0 and s["completed"] == 6


def test_transient_launch_failures_recover_bit_identical(
        tiny_parts, ref_streams):
    # 2 consecutive failures < the default 2-retry budget: invisible
    # beyond the retry counter
    eng = _build(tiny_parts, faults=FaultPlan(fail_launches={(2, 0): 2}))
    s = _drain(eng, _prompts(tiny_parts[0]))
    assert s["launch_retries"] > 0 and s["failed"] == 0
    assert s["completed"] == 6
    assert _streams(eng) == ref_streams


def test_retry_exhaustion_fails_one_not_the_run(tiny_parts, ref_streams):
    # every launch at tick 2 fails persistently: each exhausted launch
    # sacrifices one victim; the engine and the other requests survive
    eng = _build(tiny_parts, faults=FaultPlan(fail_launches={(2, 0): 99}))
    s = _drain(eng, _prompts(tiny_parts[0]))
    assert s["failed"] >= 1
    assert s["failed"] + s["completed"] == 6
    survivors = {r.rid: list(r.tokens) for r in eng.requests
                 if r.state is RequestState.DONE}
    assert survivors and all(ref_streams[rid] == t
                             for rid, t in survivors.items())


def _fail_nth_launch(eng, n: int, message: str):
    """Make the fast tier's n-th ragged launch raise a jax runtime error
    carrying ``message`` (its status comes first, as the runtime's do)."""
    rt = eng.runtimes[0]
    launch, calls = rt.ragged_fn, [0]

    def failing(*args):
        calls[0] += 1
        if calls[0] == n:
            raise jax.errors.JaxRuntimeError(message)
        return launch(*args)

    rt.ragged_fn = failing


@pytest.mark.parametrize("status", ["RESOURCE_EXHAUSTED", "INTERNAL"])
def test_device_error_ends_the_run(tiny_parts, status):
    # an OOM or a kernel fault fails the same way on every relaunch: it
    # must end the run, not be retried into one sacrificed request
    eng = _build(tiny_parts)
    _fail_nth_launch(eng, 3, f"{status}: injected device fault")
    with pytest.raises(jax.errors.JaxRuntimeError, match=status):
        _drain(eng, _prompts(tiny_parts[0]))
    s = eng.metrics.summary()
    assert s["launch_retries"] == 0 and s["failed"] == 0


def test_unavailable_device_error_is_retried(tiny_parts, ref_streams):
    eng = _build(tiny_parts)
    _fail_nth_launch(eng, 3, "UNAVAILABLE: injected transfer drop")
    s = _drain(eng, _prompts(tiny_parts[0]))
    assert s["launch_retries"] == 1 and s["failed"] == 0
    assert _streams(eng) == ref_streams


def test_escalation_storm_forces_routing_not_tokens(tiny_parts,
                                                    ref_streams):
    # δ=0 never escalates; the storm forces every gate decision up.
    # Tier-0 streams are still bit-identical to the fault-free run
    # (storms change routing, not a tier's deterministic decode).
    eng = _build(tiny_parts, tiers=2, deltas=[0.0],
                 faults=FaultPlan(storms=(Storm(1, 1000, 0),)))
    s = _drain(eng, _prompts(tiny_parts[0]))
    assert all(r.tier == 1 for r in eng.requests)
    assert all(list(r.tokens_by_tier[0]) == ref_streams[r.rid]
               for r in eng.requests)
    assert s["completed"] == 6
    # gate stats saw the forced decisions like real traffic
    assert s["escalation_rates"][0] == 1.0


def test_combo_chaos_no_deadlock_and_survivor_identity(tiny_parts,
                                                       ref_streams):
    # shrink + storm + probabilistic transient launch failures at once,
    # two tiers, over-subscribed arena with preemption
    plan = FaultPlan(seed=11,
                     shrinks=(Shrink(tick=3, tier=0, blocks=6,
                                     restore_tick=9),),
                     storms=(Storm(4, 7, 0),),
                     launch_fail_prob=0.2)
    eng = _build(tiny_parts, tiers=2, slots=4, kv_blocks=[14, None],
                 preemption_policy="youngest", faults=plan)
    s = _drain(eng, _prompts(tiny_parts[0]))       # asserts conservation
    assert s["completed"] + s["failed"] == 6
    # retries absorbed every probabilistic fault (attempts=1 < budget)
    assert s["failed"] == 0 and s["launch_retries"] > 0
    # tier-0 streams of every request match the fault-free oracle
    assert all(list(r.tokens_by_tier[0]) == ref_streams[r.rid]
               for r in eng.requests)
    assert len(plan.log) > 0                        # faults actually fired


def test_fault_determinism_same_seed_same_run(tiny_parts):
    def chaos():
        plan = FaultPlan(seed=5, launch_fail_prob=0.3,
                         shrinks=(Shrink(tick=2, tier=0, blocks=4,
                                         restore_tick=6),))
        eng = _build(tiny_parts, slots=4, kv_blocks=14,
                     preemption_policy="fewest-tokens", faults=plan)
        s = _drain(eng, _prompts(tiny_parts[0]))
        return _streams(eng), plan.log, s["preemptions"], \
            s["launch_retries"]
    assert chaos() == chaos()


def test_drain_failure_reports_diagnostics(tiny_parts):
    eng = _build(tiny_parts)
    for p in _prompts(tiny_parts[0], n=3):
        eng.submit(p)
    with pytest.raises(RuntimeError) as exc:
        eng.run(max_steps=1)
    msg = str(exc.value)
    assert "did not drain" in msg
    assert "queued=" in msg and "live_rows=" in msg
    assert "stalled_rows=" in msg and "free_blocks_by_shard=" in msg


# ---------------------------------------------------------------------------
# serve_async CLI: overload flags and KeyboardInterrupt handling
# ---------------------------------------------------------------------------


class _InterruptingClock(VirtualClock):
    """Raises KeyboardInterrupt after `ticks` engine steps."""

    def __init__(self, ticks):
        super().__init__()
        self._left = ticks

    def step_done(self):
        super().step_done()
        self._left -= 1
        if self._left <= 0:
            raise KeyboardInterrupt


def _cli_args(tmp_path, *extra):
    from repro.launch import serve_async
    return serve_async.make_parser().parse_args([
        "--requests", "8", "--rate", "4", "--slots", "2",
        "--prompt-len", "16", "--gen-len", "4", "--prefill-chunk", "8",
        "--kv-block-size", "4", "--expensive", "gemma3-1b",
        "--virtual-clock", "--retry-backoff", "0", *extra])


def test_serve_async_overload_flags(tmp_path, capsys):
    from repro.launch import serve_async
    args = _cli_args(tmp_path, "--kv-blocks", "14",
                     "--preemption", "youngest", "--deadline", "64",
                     "--inject-faults", "launchat=3:0:1")
    s = serve_async.run(args, clock=VirtualClock())
    assert s["conservation"]["ok"] and not s["interrupted"]
    assert s["preemption_policy"] == "youngest"
    assert s["faults"]["fail_launches"] == {"3:0": 1}
    assert s["launch_retries"] >= 1
    serve_async.report(s)
    assert "overload [youngest]" in capsys.readouterr().out


def test_serve_async_keyboard_interrupt_partial_summary(tmp_path):
    from repro.launch import serve_async
    trace = tmp_path / "trace.json"
    args = _cli_args(tmp_path, "--trace-out", str(trace))
    s = serve_async.run(args, clock=_InterruptingClock(4))
    assert s["interrupted"]
    assert s["completed"] < 8                  # stopped mid-run
    assert trace.exists() and s["trace_events"] > 0


# ---------------------------------------------------------------------------
# chaos x prefix cache: shrink and preemption storms against a warm index
# ---------------------------------------------------------------------------


def _shared_prompts(cfg, n=8, seed=0):
    """Prompts agreeing on their first 9 tokens (warm prefix-cache
    traffic) with unique 3-token tails."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
    out = []
    for _ in range(n):
        p = base.copy()
        p[9:] = rng.integers(0, cfg.vocab_size, 3)
        out.append(p)
    return out


@pytest.fixture(scope="module")
def shared_ref_streams(tiny_parts):
    """Fault-free, cache-off reference streams for the shared-prefix
    workload (the chaos x prefix-cache oracle)."""
    eng = _build(tiny_parts, slots=4)
    _drain(eng, _shared_prompts(tiny_parts[0]))
    return _streams(eng)


def _checked_shrink(pool):
    """Wrap `pool.shrink` to audit, at every shrink, that withheld
    blocks are never referenced (shrink draws from the free list only —
    a refcount > 0 block must never be pulled out from under a reader)
    and that the full allocator invariant suite still holds."""
    from tests.test_slots_properties import check_invariants
    orig = pool.shrink

    def shrink(n):
        took = orig(n)
        withheld = {b for lst in pool.blocks._reserved for b in lst}
        live = set(pool.blocks._refcount)
        assert not (withheld & live), \
            f"shrink withheld referenced blocks {withheld & live}"
        check_invariants(pool)
        return took

    pool.shrink = shrink
    return pool


def test_shrink_against_warm_prefix_cache(tiny_parts, shared_ref_streams):
    """Mid-run pool shrinkage while the prefix index is warm: withheld
    blocks must all be unreferenced (free-list only), streams stay
    bit-identical, and conservation holds at drain."""
    from tests.test_slots_properties import check_invariants
    plan = FaultPlan(seed=3, shrinks=(Shrink(tick=3, tier=0, blocks=6,
                                             restore_tick=10),))
    eng = _build(tiny_parts, slots=4, kv_blocks=14, prefix_cache=True,
                 preemption_policy="youngest", faults=plan)
    _checked_shrink(eng.runtimes[0].pool)
    s = _drain(eng, _shared_prompts(tiny_parts[0]))
    assert s["completed"] == 8 and s["failed"] == 0
    assert _streams(eng) == shared_ref_streams
    assert any(e[1] == "shrink" for e in plan.log)     # shrink fired
    check_invariants(eng.runtimes[0].pool)


def test_preemption_storm_against_warm_prefix_cache(tiny_parts,
                                                    shared_ref_streams):
    """Preemption churn on an over-subscribed arena with the cache on:
    releasing a victim whose blocks the index still references reclaims
    nothing out from under a reader, replays may legitimately re-hit the
    cache, and every stream matches the fault-free cache-off oracle."""
    from tests.test_slots_properties import check_invariants
    eng = _build(tiny_parts, slots=4, kv_blocks=16, prefix_cache=True,
                 preemption_policy="youngest")
    s = _drain(eng, _shared_prompts(tiny_parts[0]))
    assert s["completed"] == 8 and s["failed"] == 0
    assert _streams(eng) == shared_ref_streams
    assert s["prefix_cache"]["hits"] > 0               # the cache was warm
    assert s["preemptions"] > 0                        # churn really hit it
    check_invariants(eng.runtimes[0].pool)


def test_combo_chaos_with_prefix_cache(tiny_parts, shared_ref_streams):
    """The full storm: shrink + escalation storm + probabilistic launch
    failures, two tiers, over-subscribed tier-0 arena, preemption, and
    the prefix cache on in both tiers.  Tier-0 streams of every request
    still match the fault-free cache-off oracle and both pools'
    invariants hold at drain."""
    from tests.test_slots_properties import check_invariants
    plan = FaultPlan(seed=11,
                     shrinks=(Shrink(tick=3, tier=0, blocks=6,
                                     restore_tick=9),),
                     storms=(Storm(4, 7, 0),),
                     launch_fail_prob=0.2)
    eng = _build(tiny_parts, tiers=2, slots=4, kv_blocks=[14, None],
                 prefix_cache=True, preemption_policy="youngest",
                 faults=plan)
    _checked_shrink(eng.runtimes[0].pool)
    s = _drain(eng, _shared_prompts(tiny_parts[0]))
    assert s["completed"] + s["failed"] == 8
    assert all(list(r.tokens_by_tier[0]) == shared_ref_streams[r.rid]
               for r in eng.requests)
    assert len(plan.log) > 0
    for rt in eng.runtimes:
        check_invariants(rt.pool)


# ---------------------------------------------------------------------------
# chaos x speculative cascade decoding: shrink + preemption churn while
# the expensive tier verifies drafted tokens on provisional KV
# ---------------------------------------------------------------------------


def test_speculation_chaos_matches_k0_oracle(tiny_parts):
    """Speculative decoding under pool shrinkage and preemption churn on
    BOTH over-subscribed arenas: draft rows are retained cheap-tier rows
    and rejected verify suffixes are provisional KV writes, so the chaos
    suite's two guarantees must survive them — the slots invariant
    checker stays green on every pool, and streams (and terminal states)
    are bit-identical to the k=0 escalation-only oracle.  δ=1.0
    escalates every request, so the verify path sees all six; greedy
    acceptance emits scoring-tier argmaxes only, which is why parity
    holds at k>0, not just k=0."""
    from tests.test_slots_properties import check_invariants

    def chaos(k):
        plan = FaultPlan(seed=7,
                         shrinks=(Shrink(tick=3, tier=0, blocks=5,
                                         restore_tick=9),
                                  Shrink(tick=5, tier=1, blocks=5,
                                         restore_tick=11)))
        eng = _build(tiny_parts, tiers=2, slots=4, kv_blocks=[14, 14],
                     deltas=[1.0], preemption_policy="youngest",
                     faults=plan, speculation_k=k,
                     spec_delta=0.0 if k else None)
        _checked_shrink(eng.runtimes[0].pool)
        _checked_shrink(eng.runtimes[1].pool)
        s = _drain(eng, _prompts(tiny_parts[0]))
        for rt in eng.runtimes:
            check_invariants(rt.pool)
            # no draft row leaks a binding past drain
            assert all(r is None for r in rt.draft_req)
        assert any(e[1] == "shrink" for e in plan.log)
        return eng, s

    oracle_eng, oracle = chaos(0)
    assert oracle["completed"] == 6
    for k in (2, 4):
        eng, s = chaos(k)
        assert s["completed"] == 6 and s["failed"] == 0
        assert _streams(eng) == _streams(oracle_eng)
        assert {r.rid: r.state for r in eng.requests} \
            == {r.rid: r.state for r in oracle_eng.requests}
        sp = s["speculation"]
        assert sp["drafted"] > 0
        assert sp["drafted"] == sp["accepted"] + sp["rolled_back"]
