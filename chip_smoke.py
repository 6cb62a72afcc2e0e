#!/usr/bin/env python3
"""Bring-up check of the cascade server on TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # tier meshes over four chips

One chip, in one process:

(a) the Pallas kernels of the serving path, compiled (never interpreted),
    against their jnp oracles in ``repro.kernels.ref`` at both tiers'
    shapes: ``confidence_gate`` over each tier's full vocabulary,
    ``ragged_attention`` and ``paged_attention`` at each tier's head
    layout, all in bfloat16;
(b) the default cascade, gemma3-1b -> phi4-mini-3.8b at published widths
    in bfloat16, served through ``repro.launch.serve_async``'s
    ``build_engine``/``run`` with lognormal prompts up to 512 tokens;
(c) the serving checks: every request completed and generated
    ``--gen-len`` tokens, none failed or shed, no launch was retried, no
    program compiled mid-run, request conservation holds.

``--four-chips`` runs only the sharded path: the phase (b) workload, at a
fixed gate threshold and under a virtual clock, with each tier's rows and
KV pool sharded over its own two chips (``--tier-mesh 2x1 2x1``), then
the same workload on one chip; the tiers must sit on disjoint chips and
both runs must serve identical token streams (equal ``stream_checksum``).

Params are random from ``--seed``; prompts come from
``repro.data.bigram_lm``.  Nothing is read from outside the checkout.  The
last line of stdout is ``{"ok": true, "device": {...}}``; a failed check,
or a machine without a TPU, exits non-zero without printing it.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

# the served cascade (phase b); --flat-buckets keeps warmup at two
# compiled widths per tier: decode-only ticks and everything else
SERVE_ARGS = [
    "--fast", "gemma3-1b", "--expensive", "phi4-mini-3.8b",
    "--variant", "full", "--requests", "8", "--rate", "8", "--slots", "8",
    "--prompt-len", "512", "--length-dist", "lognormal", "--gen-len", "16",
    "--escalation-budget", "0.5", "--flat-buckets", "16", "512",
]

# oracle tolerances.  The gate reads the same bf16 logits as its oracle
# and computes in float32: only reduction order differs.  Attention
# outputs are bf16 (one ulp is 2^-8 relative) after float32 accumulation.
GATE_ATOL = 1e-3
ATTN_ATOL, ATTN_RTOL = 2e-2, 2e-2

# (tier, vocab, kv_heads, q_per_kv, head_dim, sliding window)
TIER_SHAPES = [("gemma3-1b", 262144, 1, 4, 256, 512),
               ("phi4-mini-3.8b", 200064, 8, 3, 128, None)]


class Checks:
    def __init__(self):
        self.failed = []

    def __call__(self, ok: bool, what: str) -> None:
        print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
        if not ok:
            self.failed.append(what)


def kernel_parity(check: Checks, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    rows, slots, bs, max_seq = 24, 8, 16, 528
    pages = -(-max_seq // bs)
    nblocks = slots * pages + 1

    def bf16(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape, np.float32) * scale,
                           jnp.bfloat16)

    def worst(out, want):
        out = np.asarray(out, np.float32)
        want = np.asarray(want, np.float32)
        err = np.abs(out - want)
        excess = err - (ATTN_ATOL + ATTN_RTOL * np.abs(want))
        return float(err.max()), bool((excess <= 0).all())

    def page_table(depths):
        perm = rng.permutation(np.arange(1, nblocks))
        pt = np.zeros((slots, pages), np.int32)
        at = 0
        for r, d in enumerate(depths):
            n = -(-int(d) // bs)
            pt[r, :n] = perm[at:at + n]
            at += n
        return jnp.asarray(pt)

    for tier, vocab, kv, g, hd, window in TIER_SHAPES:
        # -- confidence_gate over the tier's whole vocabulary -------------
        logits = bf16(rows, vocab, scale=4.0)
        got = jax.block_until_ready(
            ops.confidence_gate(logits, interpret=False))
        want = ref.confidence_gate_ref(logits)
        errs = {k: float(jnp.max(jnp.abs(got[k] - want[k])))
                for k in ("conf", "entropy", "logz")}
        same = int(jnp.sum(got["argmax"] == want["argmax"]))
        check(all(e <= GATE_ATOL for e in errs.values()) and same == rows,
              f"confidence_gate {tier} [{rows}, {vocab}] bf16: "
              + " ".join(f"max|d{k}| {v:.2e}" for k, v in errs.items())
              + f" argmax {same}/{rows} equal (tol {GATE_ATOL})")

        kp, vp = bf16(nblocks, bs, kv, hd), bf16(nblocks, bs, kv, hd)

        # -- ragged_attention: one mixed prefill + decode flat batch -------
        q_len = np.array([200, 1, 1, 64, 0, 1, 128, 1], np.int32)
        q_start = np.array([0, 527, 300, 400, 0, 17, 390, 100], np.int32)
        width = 512
        pt = page_table(q_start + q_len)
        q = bf16(width, kv, g, hd)
        args = (q, kp, vp, pt, jnp.asarray(q_start), jnp.asarray(q_len))
        got = jax.block_until_ready(ops.ragged_attention(
            *args, window=window, interpret=False))
        err, ok = worst(got, ref.ragged_attention_ref(*args, window=window))
        check(ok, f"ragged_attention {tier} W={width} KV={kv} G={g} "
                  f"hd={hd} window={window} bf16: max|d| {err:.2e} "
                  f"(tol {ATTN_ATOL} + {ATTN_RTOL}|ref|)")

        # -- paged_attention: one decode token per row ---------------------
        pos = rng.integers(0, max_seq, slots).astype(np.int32)
        pt = page_table(pos + 1)
        q = bf16(slots, kv, g, hd)
        args = (q, kp, vp, pt, jnp.asarray(pos))
        got = jax.block_until_ready(ops.paged_attention(
            *args, window=window, interpret=False))
        err, ok = worst(got, ref.paged_attention_ref(*args, window=window))
        check(ok, f"paged_attention {tier} B={slots} KV={kv} G={g} "
                  f"hd={hd} window={window} bf16: max|d| {err:.2e} "
                  f"(tol {ATTN_ATOL} + {ATTN_RTOL}|ref|)")


def serve(argv, *, virtual_clock: bool) -> dict:
    from repro.launch import serve_async
    from repro.serving.engine import VirtualClock

    args = serve_async.make_parser().parse_args(argv)
    t0 = time.perf_counter()
    summary = serve_async.run(
        args, clock=VirtualClock() if virtual_clock else None)
    summary["wall_s"] = time.perf_counter() - t0
    gc.collect()            # drop the engine's params before the next run
    return summary


def check_served(check: Checks, s: dict, label: str) -> None:
    import math

    gen_len = s["gen_len"]
    check(s["completed"] == s["requests"] and s["failed"] == 0
          and s["shed"] == 0,
          f"{label}: completed {s['completed']}/{s['requests']}, "
          f"failed {s['failed']}, shed {s['shed']}")
    check(s["tokens_served"] == s["requests"] * gen_len,
          f"{label}: {s['tokens_served']} tokens served "
          f"({s['requests']} x {gen_len})")
    check(s["launch_retries"] == 0,
          f"{label}: launch retries {s['launch_retries']}")
    check(s["mid_run_recompiles"] == 0,
          f"{label}: mid-run recompiles {s['mid_run_recompiles']}")
    check(bool(s["conservation"]["ok"]),
          f"{label}: conservation {s['conservation']}")
    check(all(math.isfinite(d) and 0.0 <= d <= 1.0 for d in s["delta"]),
          f"{label}: gate thresholds {s['delta']} finite in [0, 1]")


def report_served(s: dict, label: str) -> None:
    rates = ", ".join(f"{r:.3f}" for r in s["escalation_rates"])
    print(f"{label}: {s['tokens_served']} tokens for {s['completed']} "
          f"requests in {s['elapsed']:.3f} s of serving "
          f"({s['wall_s']:.1f} s with params and compiles), "
          f"{s['steps']} ticks, escalation rate [{rates}], "
          f"tier requests {s['tier_requests']}, "
          f"params bytes {s['params_bytes']}, "
          f"stream checksum {s['stream_checksum'][:16]}", flush=True)


def one_chip(check: Checks, seed: int) -> None:
    import jax

    print("phase (a): compiled kernels vs jnp oracles", flush=True)
    t0 = time.perf_counter()
    kernel_parity(check, seed)
    print(f"phase (a) took {time.perf_counter() - t0:.1f} s", flush=True)

    print("phase (b): default cascade at published widths", flush=True)
    s = serve(SERVE_ARGS + ["--seed", str(seed)], virtual_clock=False)
    report_served(s, "served")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
          f"bytes_limit {stats.get('bytes_limit')}", flush=True)
    print("phase (c): serving checks", flush=True)
    check_served(check, s, "served")
    check(len(s["tier_requests"]) == 2 and min(s["tier_requests"]) > 0,
          f"both tiers served: tier requests {s['tier_requests']}")


def four_chips(check: Checks, seed: int) -> None:
    import jax

    check(len(jax.devices()) >= 4,
          f"four chips present: {len(jax.devices())}")
    if check.failed:
        return
    # a budget gate's δ depends on the order in which one tick's gate
    # decisions land, and row placement differs between the two layouts;
    # a fixed δ of 1.0 takes every request through both tiers instead
    i = SERVE_ARGS.index("--escalation-budget")
    argv = (SERVE_ARGS[:i] + ["--delta", "1.0"] + SERVE_ARGS[i + 2:]
            + ["--seed", str(seed)])
    print("four chips: tiers sharded over 2x1 and 2x1 meshes", flush=True)
    sharded = serve(argv + ["--tier-mesh", "2x1", "2x1"], virtual_clock=True)
    report_served(sharded, "sharded")
    for t in sharded["tier_meshes"]:
        print(f"  {t['tier']}: mesh {t['mesh']} devices {t['device_ids']} "
              f"params on {t['param_device_ids']} "
              f"kv on {t['kv_device_ids']}", flush=True)
    held = [set(t["param_device_ids"]) | set(t["kv_device_ids"])
            for t in sharded["tier_meshes"]]
    check(all(len(h) == 2 for h in held) and not (held[0] & held[1]),
          f"tiers on disjoint chip pairs: {[sorted(h) for h in held]}")
    check_served(check, sharded, "sharded")
    print("four chips: the same workload on one chip", flush=True)
    single = serve(argv, virtual_clock=True)
    report_served(single, "one chip")
    check_served(check, single, "one chip")
    check(sharded["stream_checksum"] == single["stream_checksum"],
          "sharded and one-chip streams identical: "
          f"{sharded['stream_checksum'][:16]} vs "
          f"{single['stream_checksum'][:16]}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tier-mesh path over four chips and "
                         "its one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (jax platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import use_compile_cache

    compile_s = [0.0]
    cache = {"hits": 0, "misses": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration

    def on_event(event, **_):
        for k in cache:
            if event == f"/jax/compilation_cache/cache_{k}":
                cache[k] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    cache_dir = use_compile_cache()
    print(f"device {dev.device_kind} x{len(jax.devices())}, jax "
          f"{jax.__version__}, compile cache {cache_dir}", flush=True)

    check = Checks()
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(check, args.seed)
    print(f"total {time.perf_counter() - t0:.1f} s, backend compile "
          f"{compile_s[0]:.1f} s, persistent cache hits {cache['hits']} "
          f"misses {cache['misses']}", flush=True)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
