"""Asynchronous cascade serving under Poisson traffic.

Drives :class:`repro.serving.CascadeEngine` with open-loop arrivals:
requests arrive at rate ``--rate`` req/s (exponential inter-arrival
times), are admitted into ``--slots`` KV slots per tier as they free up
(continuous batching), and low-confidence sequences are escalated to the
expensive tier through packed escalation queues.

Real traffic has mixed prompt lengths: ``--length-dist
{uniform,lognormal,bimodal}`` samples a per-request length in
``[--min-prompt-len, --prompt-len]`` and the engine's chunked paged
prefill (``--prefill-chunk`` tokens per row per tick, admission capped at
``--prefill-token-budget`` tokens per tier per tick) serves them with no
cross-row padding beyond each row's last chunk.  Each tick runs as ONE
unified prefill+decode program per tier — by default the **ragged flat
token-batch** program, whose live tokens pack contiguously into a
``[1, W]`` batch at a bucketed power-of-two width (``--flat-buckets``
overrides the bucket set) so compute is O(live tokens);
``--no-ragged-step`` keeps the padded ``[slots, width]`` mixed program
and ``--split-step`` the legacy two-launch chunk+decode pair (the A/B
baselines; the summary reports realized launches/tick, the wasted-slot
ratio, and the compiled-program count either way).  ``--dense-kv`` or
``--no-chunked-prefill`` fall back to the uniform packed prefill
(uniform lengths only).

``--prefix-cache`` turns on refcounted KV prefix sharing (chunked paged
prefill only): each shard's pool indexes finished prompt chunks at block
boundaries, later requests with the same leading tokens map those blocks
read-only and start prefill at the first uncached chunk (cached tokens
cost 0 admission budget); writes past a shared prefix copy-on-write into
fresh blocks.  ``--shared-prefix-frac F`` makes the synthetic workload
exercise it: every request's first ``F``·length tokens come from one
shared base prompt (system-prompt traffic), the rest stay unique.  Token
streams are bit-identical with the cache on or off under a fixed
``--delta``; the summary records the hit rate, cached-token fraction,
and a stream checksum for cache-A/B comparison.

The gate threshold is set from an escalation *budget* by default
(δ = the budget-quantile of recently observed sequence confidences —
the operator caps cost, the runtime finds δ); pass ``--delta`` for a
fixed threshold instead.

Multi-device hosts can give each tier its own mesh: ``--tier-mesh 4x1
4x1`` runs the fast tier on the first four devices and the expensive
tier on the next four, request rows and the paged KV block pool sharded
over each mesh's data axis (``--shard-params`` additionally
tensor-shards params over 'model').  Token streams are bit-identical to
the single-device engine.

    PYTHONPATH=src python -m repro.launch.serve_async \
        --requests 64 --rate 8 --slots 8 --length-dist lognormal

Reports p50/p95 latency, time-to-first-token (overall and per
prompt-length bucket), throughput, per-tier utilization, escalation
rate, per-gate streaming calibration (ECE + cheap-vs-expensive
agreement over escalation outcomes), live-vs-processed prefill token
ratio, and Eq 7 FLOPs/request vs the always-fast / always-expensive
envelopes.

Overload and failure (docs/serving.md "Overload and failure semantics"):
``--preemption {none,youngest,fewest-tokens}`` evicts-and-replays a
victim row instead of stalling when an over-subscribed KV arena
(``--kv-blocks``) runs dry; ``--deadline SEC`` gives every request an
arrival-relative completion deadline and turns on load shedding;
``--launch-retries`` / ``--retry-backoff`` bound the transient-failure
retry wrapper; ``--inject-faults SPEC`` attaches a deterministic
:class:`repro.serving.faults.FaultPlan` (pool shrinkage, escalation
storms, launch failures, slow ticks — see that module for the grammar).
Ctrl-C prints the partial metrics summary and still flushes
``--trace-out``.

Observability: ``--trace-out trace.json`` records every request's
lifecycle (QUEUED -> PREFILL -> DECODE -> ESCALATED -> DONE) and every
tick's engine phases (admit / plan / launch / device_get / gate /
finish) as a Chrome-trace timeline loadable at https://ui.perfetto.dev;
``--metrics-interval 5`` prints a streaming snapshot line every 5
engine-clock seconds; ``--jax-profile DIR`` captures a jax.profiler
trace with named per-tier launch annotations.  See docs/serving.md.
"""
from __future__ import annotations

import argparse
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import bigram_lm
from repro.models import init_params
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_tier_meshes
from repro.serving import CascadeEngine, FaultPlan, TierSpec, Tracer
from repro.serving.engine import VirtualClock, WallClock
from repro.serving.request import RequestState
from repro.serving.observability import profile_window


def parse_mesh_shape(s: str):
    """'4x2' -> (data=4, model=2); bare '4' means data-only."""
    data, _, model = s.lower().partition("x")
    return int(data), int(model or 1)


def tier_meshes(args, num_tiers: int):
    """Per-tier meshes from ``--tier-mesh`` (None: unmeshed tiers).  One
    shape is broadcast to every tier; otherwise one per tier."""
    if not args.tier_mesh:
        return [None] * num_tiers
    shapes = [parse_mesh_shape(s) for s in args.tier_mesh]
    if len(shapes) == 1:
        shapes = shapes * num_tiers
    if len(shapes) != num_tiers:
        raise ValueError(f"--tier-mesh takes 1 or {num_tiers} shapes, "
                         f"got {len(shapes)}")
    return make_tier_meshes(shapes)


def tier_params(cfg, seed: int, variant: str):
    """Random tier params from ``seed``.  The smoke tiers stay float32;
    published widths are built in bfloat16 (two float32 tiers of the
    default cascade would not fit a 16 GB chip) by one jitted program, so
    no float32 copy of a weight is ever materialized on the device."""
    key = jax.random.PRNGKey(seed)
    if variant == "smoke":
        return init_params(cfg, key, jnp.float32)
    return jax.jit(init_params, static_argnums=(0, 2))(cfg, key,
                                                      jnp.bfloat16)


def build_engine(args, clock=None, tracer=None):
    fast_cfg = get_config(args.fast, args.variant)
    exp_cfg = get_config(args.expensive, args.variant)
    fast_params = tier_params(fast_cfg, args.seed, args.variant)
    exp_seed = getattr(args, "expensive_seed", None)
    exp_params = tier_params(
        exp_cfg, args.seed + 1 if exp_seed is None else exp_seed,
        args.variant)
    gate_kw = ({"deltas": [args.delta]} if args.delta is not None
               else {"escalation_budget": args.escalation_budget})
    meshes = tier_meshes(args, 2)
    shard_params = bool(getattr(args, "shard_params", False))
    engine = CascadeEngine(
        [TierSpec(args.fast, fast_cfg, fast_params, mesh=meshes[0],
                  shard_params=shard_params),
         TierSpec(args.expensive, exp_cfg, exp_params, mesh=meshes[1],
                  shard_params=shard_params)],
        slots=args.slots, prompt_len=args.prompt_len, gen_len=args.gen_len,
        use_gate_kernel=not args.no_gate_kernel,
        use_paged_kv=not args.dense_kv, kv_block_size=args.kv_block_size,
        kv_blocks=args.kv_blocks,
        use_chunked_prefill=False if (args.no_chunked_prefill
                                      or args.dense_kv) else None,
        prefill_chunk=args.prefill_chunk,
        prefill_token_budget=args.prefill_token_budget,
        use_unified_step=False if getattr(args, "split_step", False)
        else None,
        use_ragged_step=getattr(args, "ragged_step", None),
        flat_buckets=getattr(args, "flat_buckets", None),
        prefix_cache=bool(getattr(args, "prefix_cache", False)),
        speculation_k=getattr(args, "speculate", 0) or 0,
        spec_delta=getattr(args, "spec_delta", None),
        clock=clock if clock is not None else WallClock(),
        tracer=tracer,
        profile_annotations=bool(getattr(args, "jax_profile", None)),
        preemption_policy=getattr(args, "preemption", "none"),
        launch_retries=getattr(args, "launch_retries", 2),
        retry_backoff=getattr(args, "retry_backoff", 0.02),
        faults=(FaultPlan.parse(args.inject_faults)
                if getattr(args, "inject_faults", None) else None),
        **gate_kw)
    return engine, min(fast_cfg.vocab_size, exp_cfg.vocab_size)


def poisson_arrivals(n: int, rate: float, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def sample_lengths(dist: str, n: int, max_len: int, min_len: int,
                   seed: int) -> np.ndarray:
    """Per-request prompt lengths in [min_len, max_len].

    uniform   — every prompt at max_len (the legacy uniform workload)
    lognormal — median ~ max_len/4, σ=0.8: the heavy right tail of chat /
                search traffic (most prompts short, a few near the cap)
    bimodal   — half short (~max_len/8), half long (~0.8·max_len): the
                mixed short-query + long-document pattern
    """
    if dist == "uniform":
        return np.full(n, max_len, np.int64)
    rng = np.random.default_rng(seed + 1_000_003)
    if dist == "lognormal":
        lens = rng.lognormal(mean=np.log(max(max_len / 4.0, 1.0)),
                             sigma=0.8, size=n)
    elif dist == "bimodal":
        short = rng.normal(max_len / 8.0, max_len / 16.0, size=n)
        long = rng.normal(0.8 * max_len, max_len / 10.0, size=n)
        lens = np.where(rng.random(n) < 0.5, short, long)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(lens), min_len, max_len).astype(np.int64)


def apply_shared_prefix(prompts: np.ndarray, lengths: np.ndarray,
                        frac: float, vocab: int, seed: int) -> np.ndarray:
    """Overwrite the first ``frac``·length tokens of every prompt with one
    shared base sequence (system-prompt traffic); the tail stays unique.
    ``frac=0`` is the identity, ``frac=1`` makes prompts pure prefixes of
    each other (maximal sharing)."""
    if not frac:
        return prompts
    if not 0.0 <= frac <= 1.0:
        raise ValueError(f"--shared-prefix-frac must be in [0, 1], "
                         f"got {frac}")
    base = bigram_lm(num_seqs=1, seq_len=prompts.shape[1], vocab=vocab,
                     seed=seed + 7_777_777)[0]
    out = prompts.copy()
    for i, n in enumerate(lengths):
        k = int(frac * int(n))
        out[i, :k] = base[:k]
    return out


def stream_checksum(engine) -> str:
    """Order-independent digest of every request's final (tier, state,
    token stream) — two runs serving the same workload bit-identically
    agree on it regardless of internal scheduling (the cache-A/B and
    sharded-parity oracle)."""
    h = hashlib.sha256()
    for req in sorted(engine.requests, key=lambda r: r.rid):
        h.update(f"{req.rid}:{req.tier}:{req.state.name}:".encode())
        h.update(np.asarray(req.tokens, np.int64).tobytes())
        h.update(b"|")
    return h.hexdigest()


def snapshot_line(snap: dict) -> str:
    """One-line periodic progress record (``--metrics-interval``)."""
    esc = "/".join(f"{r:.2f}" for r in snap["escalation_rates"])
    ece = "/".join("-" if np.isnan(e) else f"{e:.3f}"
                   for e in snap["gate_ece"])
    return (f"[t={snap['t']:.1f}] completed {snap['completed']}"
            f"/{snap['requests']}  steps {snap['steps']}  "
            f"esc [{esc}]  gate ece [{ece}]  "
            f"tick p50 {snap['tick_duration_p50']:.4f}")


def run(args, clock=None) -> dict:
    tracer = (Tracer(capacity=args.trace_ring)
              if getattr(args, "trace_out", None) else None)
    engine, vocab = build_engine(args, clock, tracer)
    # catches explicit flags AND the engine's auto-fallback to uniform
    # prefill (recurrent-state / frontend tiers, dense arena)
    if args.length_dist != "uniform" and not engine.chunked_prefill:
        raise ValueError(
            "mixed prompt lengths require chunked paged prefill, but the "
            "engine fell back to the uniform path (--no-chunked-prefill/"
            "--dense-kv given, or a tier carries recurrent state or a "
            "modality frontend) — use --length-dist uniform")
    prompts = bigram_lm(num_seqs=args.requests, seq_len=args.prompt_len,
                        vocab=vocab, seed=args.seed)
    lengths = sample_lengths(args.length_dist, args.requests,
                             args.prompt_len, args.min_prompt_len,
                             args.seed)
    prompts = apply_shared_prefix(
        prompts, lengths, getattr(args, "shared_prefix_frac", 0.0),
        vocab, args.seed)
    arrivals = poisson_arrivals(args.requests, args.rate, args.seed)
    # warmup compiles every tier and then resets the clock, so arrival
    # timestamps are relative to the start of serving, not construction
    engine.warmup()
    ddl = getattr(args, "deadline", None)
    for p, n, t in zip(prompts, lengths, arrivals):
        engine.submit(p[:int(n)], arrival_time=float(t),
                      deadline=None if ddl is None else float(t) + ddl)
    interval = getattr(args, "metrics_interval", None)
    on_snap = ((lambda s: print(snapshot_line(s)))
               if interval is not None else None)
    profile_dir = getattr(args, "jax_profile", None)
    interrupted = False
    with profile_window(profile_dir):
        try:
            summary = engine.run(metrics_interval=interval,
                                 on_snapshot=on_snap)
        except KeyboardInterrupt:
            # graceful stop: report what completed and still flush the
            # trace below, instead of dying with a bare traceback
            interrupted = True
            summary = engine.metrics.summary()
            print(f"\ninterrupted at t={engine.clock.now():.2f} — partial "
                  f"summary ({summary['completed']}/{summary['requests']} "
                  "completed)")
    summary["interrupted"] = interrupted
    trace_out = getattr(args, "trace_out", None)
    if trace_out:
        n_events = tracer.export(trace_out)
        summary["trace_events"] = n_events
        summary["trace_dropped"] = tracer.dropped
        print(f"wrote {n_events} trace events to {trace_out}"
              + (f" ({tracer.dropped} dropped)" if tracer.dropped else ""))
    summary["rate"] = args.rate
    # realized offered load: completions can never beat this in an
    # open-loop run (makespan >= arrival span), a sanity bound on
    # the reported throughput
    summary["offered_rate"] = (
        args.requests / float(arrivals[-1] - arrivals[0])
        if args.requests > 1 and arrivals[-1] > arrivals[0]
        else float("nan"))
    summary["slots"] = args.slots
    summary["gen_len"] = args.gen_len
    summary["length_dist"] = args.length_dist
    summary["max_prompt_len"] = args.prompt_len
    summary["prefill_chunk"] = (engine.prefill_chunk
                                if engine.chunked_prefill else None)
    summary["chunked_prefill"] = engine.chunked_prefill
    summary["unified_step"] = engine.unified_step
    summary["ragged_step"] = engine.ragged_step
    summary["flat_buckets"] = [rt.flat_buckets if rt.ragged else None
                               for rt in engine.runtimes]
    # compiled-program accounting: warmed vs launched widths per tier
    # (mid_run_recompiles nonzero means a tick launched a width warmup
    # never compiled — the failure mode the bucketed layout eliminates)
    summary["compiled_programs"] = engine.compile_stats()
    summary["mid_run_recompiles"] = sum(
        len(c["mid_run_recompiles"]) for c in summary["compiled_programs"])
    summary["admitted_tokens_by_tier"] = \
        list(engine.scheduler.admitted_tokens)
    summary["escalation_budget"] = (None if args.delta is not None
                                    else args.escalation_budget)
    summary["delta"] = [engine.scheduler.delta(g)
                        for g in range(len(engine.scheduler.gates))]
    # block-paged KV arena accounting (high-water = blocks actually
    # mapped at peak, the number the paged arena saves vs dense; sharded
    # pools additionally report per-data-shard high-water)
    # overload & failure knobs, for the BENCH json and the report line
    summary["speculation_k"] = engine.speculation_k
    summary["spec_delta"] = engine.spec_delta
    summary["preemption_policy"] = engine.preemption_policy
    summary["deadline"] = ddl
    if engine.faults is not None:
        summary["faults"] = engine.faults.describe()
        summary["fault_events"] = len(engine.faults.log)
    summary["kv_arena"] = engine.memory_stats()
    # prefix-cache A/B provenance: config knobs plus an order-independent
    # digest of every final token stream (bit-identity oracle)
    summary["prefix_cache_enabled"] = engine.prefix_cache
    summary["shared_prefix_frac"] = float(
        getattr(args, "shared_prefix_frac", 0.0) or 0.0)
    summary["stream_checksum"] = stream_checksum(engine)
    # sharded serving: per-tier mesh layout (None entries: single-device)
    summary["tier_meshes"] = engine.mesh_topology()
    summary["device_count"] = jax.device_count()
    summary["params_bytes"] = [
        sum(x.nbytes for x in jax.tree.leaves(t.params))
        for t in engine.tiers]
    summary["tokens_served"] = sum(
        len(r.tokens) for r in engine.requests
        if r.state is RequestState.DONE)
    return summary


def report(s: dict) -> None:
    unit = "s"
    print(f"served {s['completed']}/{s['requests']} requests "
          f"in {s['elapsed']:.2f}{unit} over {s['steps']} engine steps "
          f"(rate {s['rate']}/s, {s['slots']} slots/tier)")
    if any(t["mesh"] for t in s.get("tier_meshes", [])):
        print("  meshes " + "  ".join(
            f"{t['tier']}={t['mesh']}" for t in s["tier_meshes"]))
    print(f"  latency  p50 {s['latency_p50']:.3f}{unit}  "
          f"p95 {s['latency_p95']:.3f}{unit}   "
          f"ttft p50 {s['ttft_p50']:.3f}{unit}  p95 {s['ttft_p95']:.3f}{unit}")
    if s.get("chunked_prefill"):
        buckets = "  ".join(f"{b}:{v:.3f}{unit}" for b, v in
                            s["ttft_p50_by_prompt_bucket"].items())
        print(f"  prompts {s['length_dist']} (mean {s['prompt_len_mean']:.1f}"
              f"/{s['max_prompt_len']} tok, chunk {s['prefill_chunk']})  "
              f"live-token ratio {s['prefill_live_token_ratio']:.3f}")
        print(f"  ttft p50 by prompt bucket  {buckets}")
    print(f"  throughput {s['throughput']:.2f} req/{unit}   "
          f"tier utilization "
          + "  ".join(f"{n}={u:.2f}" for n, u in
                      zip(s['tier_names'], s['tier_utilization'])))
    # realized launch efficiency: compiled-program dispatches and
    # blocking device_gets per engine tick, per tier (the unified
    # token-batch path's budget is one of each per active tier per tick)
    mode = ("ragged" if s.get("ragged_step")
            else "unified" if s.get("unified_step") else "split")
    print(f"  launches/tick [{mode}] "
          + "  ".join(f"{n}={l:.2f}" for n, l in
                      zip(s["tier_names"], s["launches_per_tick"]))
          + "   host-syncs/tick "
          + "  ".join(f"{n}={h:.2f}" for n, h in
                      zip(s["tier_names"], s["host_syncs_per_tick"])))
    if s.get("step_processed_tokens"):
        cp = s.get("compiled_programs") or []
        progs = "  ".join(f"{c['tier']}={c['compiled_programs']}"
                          for c in cp)
        recomp = s.get("mid_run_recompiles", 0)
        print(f"  token slots  live {s['step_live_tokens']}"
              f"/{s['step_processed_tokens']} processed "
              f"(wasted-slot ratio {s['wasted_slot_ratio']:.3f})   "
              f"compiled programs {progs}"
              + (f"   MID-RUN RECOMPILES {recomp}" if recomp else ""))
    overloaded = (s.get("shed") or s.get("failed") or s.get("preemptions")
                  or s.get("launch_retries")
                  or s.get("preemption_policy", "none") != "none"
                  or s.get("interrupted"))
    if overloaded:
        cons = s.get("conservation", {})
        print(f"  overload [{s.get('preemption_policy', 'none')}]  "
              f"shed {s.get('shed', 0)} "
              f"(rate {s.get('shed_rate', 0.0):.3f})  "
              f"preempted {s.get('preemptions', 0)} "
              f"(replayed {s.get('replayed_tokens', 0)} tok)  "
              f"failed {s.get('failed', 0)}  "
              f"launch retries {s.get('launch_retries', 0)}  "
              "conservation "
              + ("ok" if cons.get("ok")
                 else ("interrupted" if s.get("interrupted")
                       else f"VIOLATED ({cons})")))
    pc = s.get("prefix_cache") or {}
    if s.get("prefix_cache_enabled") and pc.get("lookups"):
        shared_hw = sum(t.get("kv_shared_high_water_blocks", 0)
                        for t in s.get("kv_arena", [])
                        if isinstance(t, dict))
        print(f"  prefix cache  hit rate {pc['hit_rate']:.2f} "
              f"({pc['hits']}/{pc['lookups']} admissions)  "
              f"cached tokens {pc['cached_tokens']} "
              f"({pc['cached_token_frac']:.2f} of prompt tokens)  "
              f"shared-block hw {shared_hw}")
    sp = s.get("speculation") or {}
    if s.get("speculation_k") and sp.get("drafted"):
        print(f"  speculation k={s['speculation_k']}  "
              f"accept rate {sp['accept_rate']:.2f} "
              f"({sp['accepted']}/{sp['drafted']} drafts, "
              f"{sp['rolled_back']} rolled back)")
    rates = ", ".join(f"{r:.3f}" for r in s["escalation_rates"])
    deltas = ", ".join(f"{d:.4f}" for d in s["delta"])
    target = ("" if s.get("escalation_budget") is None
              else f" (budget target {s['escalation_budget']:.3f})")
    print(f"  escalation rate [{rates}] at δ=[{deltas}]{target}")
    cal = s.get("gate_calibration") or []
    if cal:
        # streaming calibration against the escalation-outcome proxy
        # (cheap-vs-expensive token agreement on escalated traffic)
        def _f(x, spec=".3f"):
            return "-" if x is None or np.isnan(x) else format(x, spec)
        print("  gate calibration "
              + "  ".join(f"g{g['gate']}: ece {_f(g['ece'])} "
                          f"agree {_f(g['agreement_rate'], '.2f')} "
                          f"({g['outcomes']} outcomes)" for g in cal))
    print(f"  Eq7 FLOPs/request: cascade {s['flops_per_request_cascade']:.3e} "
          f"(always-fast {s['flops_per_request_always_fast']:.3e}, "
          f"always-expensive {s['flops_per_request_always_expensive']:.3e})")
    if s["flops_per_request_cascade"] \
            < s["flops_per_request_always_expensive"]:
        print("  cascade < always-expensive ✓")


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--fast", default="gemma3-1b")
    ap.add_argument("--expensive", default="phi4-mini-3.8b")
    ap.add_argument("--variant", default="smoke",
                    choices=("smoke", "full", "long"),
                    help="tier widths: smoke (reduced, float32), full "
                         "(published widths, bfloat16) or long "
                         "(published widths with sliding windows)")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--slots", type=int, default=8,
                    help="KV slot pool size per tier")
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="maximum prompt length (chunked prefill); exact "
                         "length under --no-chunked-prefill/--dense-kv")
    ap.add_argument("--min-prompt-len", type=int, default=1)
    ap.add_argument("--length-dist", default="uniform",
                    choices=("uniform", "lognormal", "bimodal"),
                    help="per-request prompt length distribution over "
                         "[min-prompt-len, prompt-len]")
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="prompt tokens a row advances per tick "
                         "(chunked paged prefill)")
    ap.add_argument("--prefill-token-budget", type=int, default=None,
                    help="prompt tokens admitted per tier per tick "
                         "(default slots * prefill-chunk)")
    ap.add_argument("--no-chunked-prefill", action="store_true",
                    help="uniform one-shot packed prefill (the chunked "
                         "path's bit-exactness oracle)")
    ap.add_argument("--split-step", action="store_true",
                    help="legacy split chunk+decode launches instead of "
                         "the unified mixed token-batch program (the "
                         "launch-count A/B escape hatch; default: unified "
                         "on paged attention-only tiers)")
    ap.add_argument("--ragged-step", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="ragged flat [1, W] token-batch layout inside "
                         "unified execution: live tokens pack "
                         "contiguously at a bucketed width, so a tick's "
                         "compute is O(live tokens).  --no-ragged-step "
                         "keeps the padded [slots, width] mixed program "
                         "(the bit-identical A/B baseline).  Default: "
                         "ragged whenever unified execution is on")
    ap.add_argument("--flat-buckets", type=int, nargs="*", default=None,
                    metavar="W",
                    help="compiled flat widths for --ragged-step (default "
                         "powers of two from 8 up to slots*prefill-chunk; "
                         "widths > 16 must be multiples of the kernel's "
                         "16-token query tile, and the largest must cover "
                         "slots*prefill-chunk)")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="speculative cascade decoding: the cheap tier "
                         "drafts up to K tokens per escalated request per "
                         "tick and the expensive tier scores all drafted "
                         "positions in its one ragged launch, emitting "
                         "every accepted token (plus the bonus token) in "
                         "a single tick.  Streams stay bit-identical to "
                         "K=0 (greedy acceptance emits scoring-model "
                         "argmaxes only).  Needs the ragged step; K=0 "
                         "disables (the escalation-only oracle)")
    ap.add_argument("--spec-delta", type=float, default=None,
                    metavar="CONF",
                    help="confidence floor for *keeping* drafted tokens "
                         "(draft truncates at its first token below it); "
                         "default: the draft tier's calibrated gate "
                         "threshold δ")
    ap.add_argument("--delta", type=float, default=None,
                    help="fixed gate threshold (overrides the budget)")
    ap.add_argument("--escalation-budget", type=float, default=0.25,
                    help="target escalation rate; δ is calibrated online")
    ap.add_argument("--no-gate-kernel", action="store_true",
                    help="jnp confidence instead of the Pallas gate kernel")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="tokens per KV block (paged arena)")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="KV arena size in blocks per tier (default: fully "
                         "provisioned slots*pages_per_row+1; smaller "
                         "over-subscribes, attention-only models)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="refcounted KV prefix sharing: index finished "
                         "prompt chunks per shard, admit later requests "
                         "with matching leading tokens straight past them "
                         "(copy-on-write past the shared prefix; needs "
                         "chunked paged prefill)")
    ap.add_argument("--shared-prefix-frac", type=float, default=0.0,
                    metavar="F",
                    help="overwrite the first F·length tokens of every "
                         "prompt with one shared base sequence (synthetic "
                         "system-prompt traffic for exercising "
                         "--prefix-cache); 0 leaves prompts unique")
    ap.add_argument("--dense-kv", action="store_true",
                    help="PR 1 dense one-page-per-request arena instead of "
                         "the block-paged arena + paged decode kernel")
    ap.add_argument("--tier-mesh", nargs="*", default=None,
                    metavar="DATAxMODEL",
                    help="per-tier mesh shapes, e.g. --tier-mesh 4x1 2x2: "
                         "each tier gets its own mesh over a contiguous "
                         "slice of jax.devices() (wrapping when tiers "
                         "overrun the host); rows + KV block pool shard "
                         "over the data axis.  One shape is broadcast to "
                         "both tiers; default: no mesh (single device)")
    ap.add_argument("--shard-params", action="store_true",
                    help="tensor-shard tier params over the mesh 'model' "
                         "axis (default: replicate params per tier)")
    ap.add_argument("--preemption", default="none",
                    choices=("none", "youngest", "fewest-tokens"),
                    help="evict-and-replay policy when an over-subscribed "
                         "KV arena (--kv-blocks) runs dry: youngest evicts "
                         "the newest row on a stalled shard, fewest-tokens "
                         "the least-progressed; none keeps the stall "
                         "behaviour.  Replayed streams are bit-identical "
                         "(greedy decode)")
    ap.add_argument("--deadline", type=float, default=None, metavar="SEC",
                    help="per-request completion deadline, relative to "
                         "arrival (engine-clock units); queued requests "
                         "past — or provably unable to meet — it are shed")
    ap.add_argument("--launch-retries", type=int, default=2,
                    help="bounded retries per launch/transfer on transient "
                         "errors before sacrificing one request")
    ap.add_argument("--retry-backoff", type=float, default=0.02,
                    metavar="SEC", help="initial retry backoff (doubles "
                         "per attempt)")
    ap.add_argument("--inject-faults", default=None, metavar="SPEC",
                    help="deterministic fault plan, e.g. "
                         "'seed=7,shrink=5:0:8:40,storm=10-14:0,"
                         "launch=0.05' (see repro/serving/faults.py for "
                         "the grammar)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--expensive-seed", type=int, default=None,
                    help="param-init seed for the expensive tier "
                         "(default --seed + 1).  Setting it to --seed "
                         "with matching --fast/--expensive configs gives "
                         "identical tiers — the self-speculation "
                         "configuration the spec_ab benchmark arm uses "
                         "to measure --speculate at a known accept rate")
    ap.add_argument("--json", default=None,
                    help="also write the summary dict to this path")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON timeline of "
                         "the run: per-request lifecycle spans and "
                         "per-tick engine phases (load at ui.perfetto.dev)")
    ap.add_argument("--trace-ring", type=int, default=1 << 18,
                    help="trace ring-buffer capacity in events; oldest "
                         "events drop first (dropped count is reported)")
    ap.add_argument("--metrics-interval", type=float, default=None,
                    metavar="SEC",
                    help="print a streaming metrics snapshot (completions, "
                         "escalation, gate ECE, tick p50) every SEC "
                         "engine-clock seconds")
    ap.add_argument("--jax-profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the serving loop "
                         "into DIR (adds named run_mixed/run_chunk/"
                         "run_step annotations; view in TensorBoard or "
                         "Perfetto)")
    ap.add_argument("--virtual-clock", action="store_true",
                    help="deterministic 1-tick-per-step clock (arrival "
                         "times are then in ticks, not seconds)")
    return ap


def main() -> None:
    args = make_parser().parse_args()
    use_compile_cache()
    clock = VirtualClock() if args.virtual_clock else None
    summary = run(args, clock)
    report(summary)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=2, default=float)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
