"""Find a cell's knee once, by a sweep of fixed offered rates on the chip.

    python3 bench/sweep.py --workload stage0.chat --seconds 20 --rates 2 3 4 5 6

One engine is built and warmed; then, for each rate in turn, a fresh
batch of the cell's traffic at that rate is served and the requests that
arrived in its window are followed to their end.  One ``sweep`` JSON line
per rate: completions per second inside the window against the offered
rate, the latency quantiles, the queue left at the window's end.  The
last line, ``knee``, applies the rule: the highest rate whose completions
keep up (at least 0.93 of the offered rate, with at most 4 requests
queued at the window's end), raised to what the next rate up completed
if that is more.  The cell's rate is then fixed in
``bench/cells/<cell>.json`` (0.8 × knee for a cell judged on tails, 1.5 ×
for one judged on throughput).  The benchmark's own runs do not run this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import cells, main, window  # noqa: E402


def sweep(workload: str, seconds: float, rates, seed: int,
          drain_s: float) -> int:
    cell = cells.resolve(workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    b = main.build(cell, seed, False)
    print("setup " + json.dumps({"s": time.perf_counter() - T0}), flush=True)
    pre = cell.settings["prewindow_s"]
    rows = []
    for i, rate in enumerate(rates):
        first = len(b.engine.requests)
        main.submit(b, cell, rate, pre + seconds, seed + i)
        win = window.serve(b.engine, window.Window(pre, pre + seconds,
                                                   first_rid=first),
                           drain_s=drain_s, until_drained=True)
        lat = [r.finish_time - r.arrival_time if r.state.name == "DONE"
               else math.inf for r in win.requests]
        wait = [r.admit_times[0] - r.arrival_time if r.admit_times
                else math.inf for r in win.requests]
        span = win.last.t - win.first.t
        rows.append({
            "rate": rate, "arrived": len(win.requests),
            "completed_per_s": len(win.done_in_window) / span,
            "tokens_per_s": sum(len(r.tokens) for r in win.done_in_window)
            / span,
            "latency_p50_s": main.percentile(lat, 50),
            "latency_p95_s": main.percentile(lat, 95),
            "queue_wait_p95_s": main.percentile(wait, 95),
            "ticks_per_s": (win.last.tick - win.first.tick) / span,
            "queued_at_stop": sum(r.arrival_time <= win.last.t
                                  for r in b.engine.scheduler.queues[0]),
            "drained": win.drained,
            "escalated": sum(r.tier > 0 for r in win.requests)})
        print("sweep " + json.dumps(rows[-1]), flush=True)
        # let the engine finish whatever is left before the next rate
        while not b.engine._done():
            b.engine.step(b.engine.clock.now())
    print("knee " + json.dumps({"knee": knee(rows)}), flush=True)
    return 0


def knee(rows) -> float:
    """The highest offered rate that the cell keeps up with, from the
    sweep's rows in rising order of rate."""
    ok = [r["rate"] for r in rows if r["completed_per_s"] >= 0.93 * r["rate"]
          and r["queued_at_stop"] <= 4]
    if not ok:
        return rows[0]["completed_per_s"]
    best = max(ok)
    above = [r for r in rows if r["rate"] > best]
    if above:
        best = max(best, min(above[0]["completed_per_s"], above[0]["rate"]))
    return best


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--drain", type=float, default=30.0)
    a = ap.parse_args()
    sys.exit(sweep(a.workload, a.seconds, a.rates, a.seed, a.drain))
