"""One run of one cell: set-up, window, metrics, and the correctness
check.  ``bench/run.py`` is the command; this module is its body."""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from bench.harness import cells, compare, traffic, weights, window


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="with --trace 1, also copy the device trace and the "
                         "window's Tracer phases into DIR")
    return ap.parse_args(argv)


class CacheCounter:
    """Persistent compile-cache hits and misses, and backend compiles,
    from JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring
        self.hits = self.misses = self.compiles = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name.endswith("/compilation_cache/cache_hits"):
            self.hits += 1
        elif name.endswith("/compilation_cache/cache_misses"):
            self.misses += 1

    def _duration(self, name, _secs, **_):
        if name.endswith("backend_compile_duration"):
            self.compiles += 1


def use_cache(root: Path) -> None:
    """JAX's persistent compile cache at a fixed path inside the checkout
    (the path is part of the cache's key), every program cached."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@contextlib.contextmanager
def params_from_bench(made: dict):
    """``serve_async.build_engine`` with the benchmark's weights in place
    of its own init (the program builds everything else)."""
    from repro.launch import serve_async
    own = serve_async.tier_params
    serve_async.tier_params = lambda cfg, seed, variant: made[cfg.name]
    try:
        yield serve_async.build_engine
    finally:
        serve_async.tier_params = own


def engine_args(cell, cfgs, seed: int, trace: bool):
    """The operator's settings as ``serve_async`` flags; everything else
    (chunk, token budget, block size, retries) stays the program's
    default."""
    from repro.launch import serve_async
    s, t = cell.settings, cell.traffic
    argv = ["--fast", cfgs[0].name, "--expensive", cfgs[1].name,
            "--variant", "full", "--slots", str(s["slots"]),
            "--prompt-len", str(t["prompt"]["max"]),
            "--gen-len", str(t["answer_tokens"]),
            "--escalation-budget", str(cell.config["gate"]["escalation_budget"]),
            "--seed", str(seed % 2**31),
            "--flat-buckets", *map(str, s["flat_buckets"])]
    if cell.config.get("tier_mesh"):
        argv += ["--tier-mesh", *cell.config["tier_mesh"]]
    if trace:
        argv += ["--jax-profile", "on"]     # named launch annotations
    return serve_async.make_parser().parse_args(argv)


def percentile(values, q: float) -> float:
    """numpy's linear percentile, where a missing value (inf) is larger
    than every finite one."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return math.inf
    pos = (len(v) - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if math.isinf(v[hi]):
        return math.inf
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def end_to_end(cell, win, setup_s: float) -> dict:
    lat = [r.finish_time - r.arrival_time if r.state.name == "DONE"
           else math.inf for r in win.requests]
    span = win.last.t - win.first.t
    tokens = sum(len(r.tokens) for r in win.done_in_window)
    values = {"setup_s": setup_s,
              "latency_p50_s": percentile(lat, 50),
              "latency_p95_s": percentile(lat, 95),
              "output_tokens_per_s": tokens / span}
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def device_info(devices, used) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


_COUNTER = None


def cache_counter() -> CacheCounter:
    """The process's one listener on JAX's monitoring events."""
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CacheCounter()
    return _COUNTER


def build(cell, seed: int, trace: bool) -> SimpleNamespace:
    """Weights from the seed, the engine as ``build_engine`` makes it, and
    every compiled width warmed (compiled, or loaded from the cache)."""
    import jax
    import jax.numpy as jnp

    from repro.serving import Tracer

    use_cache(cell.root)
    cfgs = [cells.tier_config(t, cell.root) for t in cell.tiers]
    dtype = jnp.dtype(cell.config["dtype"])
    made = {c.name: weights.make(c, seed, i, dtype)
            for i, c in enumerate(cfgs)}
    jax.block_until_ready(made)
    t_params = time.perf_counter()
    tracer = Tracer() if trace else None
    args = engine_args(cell, cfgs, seed, trace)
    with params_from_bench(made) as build_engine:
        engine, vocab = build_engine(args, None, tracer)
    engine.warmup()
    return SimpleNamespace(engine=engine, vocab=vocab, made=made, cfgs=cfgs,
                           tracer=tracer, t_params=t_params,
                           t_warm=time.perf_counter())


def submit(b, cell, rate: float, horizon: float, seed: int) -> None:
    """Queue the run's traffic, then start the clock: the first arrival
    is now."""
    for q in traffic.generate(cell.traffic, rate, horizon, b.vocab, seed):
        b.engine.submit(q.prompt, arrival_time=q.arrival)
    b.engine.reset_clock()


def run(cell, seed: int, seconds: float, trace: bool, t0: float,
        out=sys.stdout, err=sys.stderr, keep=None, keep_trace=None) -> dict:
    """One run of ``cell``; prints the result line and returns it.  A
    ``keep`` dict receives the sampled requests, weights and configs
    (the control reads them)."""
    import jax

    counter = cache_counter()
    hits, misses, compiles = counter.hits, counter.misses, counter.compiles
    b = build(cell, seed, trace)
    engine, made, cfgs, tracer = b.engine, b.made, b.cfgs, b.tracer
    s = cell.settings
    tail = s["judged_on"] == "tail"
    if trace:
        # the traced run measures the traced span alone: writing the trace
        # out stalls the host for seconds, which must fall outside it
        seconds = min(seconds, s["trace_s"])
    horizon = s["prewindow_s"] + seconds + (s["drain_s"] if tail else 0.0)
    submit(b, cell, s["rate_per_s"], horizon, seed)
    t_first = time.perf_counter()
    setup = {"params_s": b.t_params - t0,
             "compile_or_load_s": b.t_warm - b.t_params,
             "traffic_s": t_first - b.t_warm,
             "cache_hits": counter.hits - hits,
             "cache_misses": counter.misses - misses,
             "backend_compiles": counter.compiles - compiles}

    win = window.Window(start=s["prewindow_s"], stop=s["prewindow_s"] + seconds)
    prof = Profile(tracer) if trace else None
    win = window.serve(engine, win, drain_s=s["drain_s"], until_drained=tail,
                       on_edge=prof.edge if prof else (lambda e: None),
                       compile_count=lambda: counter.compiles)
    used = sorted({d for rt in engine.runtimes
                   for x in jax.tree.leaves(rt.params) for d in x.devices()},
                  key=lambda d: d.id)
    devices = jax.devices()
    device = device_info(devices, used)
    failed = sum(r.state.name != "DONE" for r in win.requests) if tail else \
        sum(r.state.name in ("SHED", "FAILED") for r in win.requests)
    result = {"correct": False, "attempted": len(win.requests),
              "failed": int(failed)}
    if trace:
        from bench.harness import runview
        view = runview.RunView(cell, cfgs, engine, win, prof, used,
                               keep_trace)
        metrics, breakdown = view.per_layer(err)
        device.update(busy_s=view.device["busy_s"],
                      window_s=view.device["window_s"])
        busy_by_chip = view.trace.busy_by_chip
    else:
        metrics = end_to_end(cell, win, t_first - t0)
        breakdown = busy_by_chip = None

    # the reference runs on the weights alone: free the program's state
    requests = list(engine.requests)
    engine = b.engine = None
    gc.collect()
    in_use = max((d.memory_stats() or {}).get("bytes_in_use", 0) for d in used)
    t_ref = time.perf_counter()
    picked = compare.sample(requests, s["sample_requests"], seed)
    params = [made[c.name] for c in cfgs]
    numbers = compare.readings(picked, params, cfgs)
    if keep is not None:
        keep.update(picked=picked, params=params, cfgs=cfgs)
    limits = s["limits"]
    ok = bool(picked) and compare.judge(numbers, limits) \
        and win.compiles_in_window == 0
    result.update(correct=ok, metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    compared = {k: {"value": numbers[k], "limit": limits[k]}
                for k in compare.NUMBERS}
    compared["compiles_in_window"] = {"value": win.compiles_in_window,
                                      "limit": 0}
    result["compared"] = compared
    info = {"setup": setup, "reference_s": time.perf_counter() - t_ref,
            "bytes_in_use_at_reference": in_use,
            "sampled": {"requests": numbers["requests"],
                        "tokens": numbers["tokens"]},
            "window": {"ticks": win.last.tick - win.first.tick,
                       "arrived": len(win.requests),
                       "done_in_window": len(win.done_in_window),
                       "drained": win.drained,
                       "escalated": sum(r.tier > 0 for r in win.requests)}}
    if busy_by_chip is not None:
        info["busy_s_by_chip"] = busy_by_chip
    print("run " + json.dumps(info), file=out, flush=True)
    for k, c in compared.items():
        print(f"compared {k} {c['value']!r} limit {c['limit']!r}",
              file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return result


class Profile:
    """The device trace of the window (``--trace 1``), with the tracer's
    clock read at the same instants, so host phases and device ops can
    be put on one time line."""

    def __init__(self, tracer):
        self.tracer = tracer
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.xplane = None
        self.us = {}            # tracer clock at the window's edges

    def edge(self, name: str) -> None:
        import jax
        if name == "start":
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.us[name] = self.tracer.now_us()
        else:
            self.us[name] = self.tracer.now_us()
            jax.profiler.stop_trace()
            found = sorted(Path(self.dir).rglob("*.xplane.pb"))
            self.xplane = str(found[-1]) if found else None

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def main(argv, t0: float) -> int:
    args = parse(argv)
    cell = cells.resolve(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX sees {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    run(cell, args.seed, args.seconds, bool(args.trace), t0,
        keep_trace=args.keep_trace)
    return 0
