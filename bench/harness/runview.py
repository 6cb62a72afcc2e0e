"""What the per-layer readers (``bench/metrics/*.py``) read: the window's
requests, the Tracer's phase spans, the program's counters at the
window's edges, the reduced device trace, the peaks and the model FLOPs."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

from bench.harness import cells, main as _main, peaks, tracered


class RunView:
    def __init__(self, cell, cfgs, engine, win, prof, used, keep_trace=None):
        self.cell, self.cfgs, self.win = cell, cfgs, win
        self.requests = win.requests
        self.chips = len(used)
        self.peak = peaks.peak_for(used[0].device_kind)
        self.window_s = win.last.wall - win.first.wall
        t0, t1 = prof.us["start"], prof.us["stop"]
        self.phases = [e for e in prof.tracer.events()
                       if e.get("ph") == "X" and t0 <= e["ts"] < t1]
        traced_s = (t1 - t0) / 1e6
        self.trace = tracered.reduce(prof.xplane, [d.id for d in used],
                                     self.phases,
                                     [t.spec.name for t in engine.runtimes],
                                     traced_s)
        if keep_trace and prof.xplane:
            out = Path(keep_trace)
            out.mkdir(parents=True, exist_ok=True)
            shutil.copy(prof.xplane, out / "trace.xplane.pb")
            (out / "phases.json").write_text(json.dumps({
                "phases": self.phases, "devices": [d.id for d in used],
                "tiers": [t.spec.name for t in engine.runtimes],
                "window_s": traced_s}))
        prof.close()
        if not self.trace.chips:
            raise RuntimeError(
                f"no {tracered.OP_LINES} line on chips {[d.id for d in used]}"
                f" in the device trace ({prof.xplane}): the reduction does "
                "not match the profiler's trace")
        self.device = {"busy_s": self.trace.busy_s,
                       "window_s": self.trace.window_s}

    percentile = staticmethod(_main.percentile)

    def phase_spans(self, name: str):
        """``(ts_us, dur_us)`` of the window's Tracer phases ``name``."""
        return [(e["ts"], e["dur"]) for e in self.phases if e["name"] == name]

    def token_slots(self):
        a, b = self.win.first, self.win.last
        live = sum(b.live_tokens) - sum(a.live_tokens)
        processed = sum(b.processed_tokens) - sum(a.processed_tokens)
        return live, processed

    def model_flops(self) -> float:
        a, b = self.win.first.progress, self.win.last.progress
        total = 0.0
        for (rid, tier), (pos1, emit1) in b.items():
            pos0, emit0 = a.get((rid, tier), (0, 0))
            total += peaks.span_flops(self.cfgs[tier], pos0, pos1,
                                      emit1 - emit0)
        return total

    def kernel_share(self, kernel: str):
        t = self.trace.kernel_s.get(kernel)
        if not t or not self.trace.busy_s:
            return None
        return 100.0 * t / self.trace.busy_s

    def idle_share(self):
        if not self.trace.chips or not self.trace.window_s:
            return None
        return 100.0 * (1.0 - self.trace.busy_s / self.trace.window_s)

    def per_layer(self, err=sys.stderr):
        """The cell's per-layer metrics; one that reads nothing is left
        out of the line and named on ``err``."""
        metrics = {}
        for m in self.cell.per_layer:
            value = cells.load_reader(m["name"], self.cell.root)(self)
            if value is None:
                print(f"per_layer {m['name']} read nothing", file=err,
                      flush=True)
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        return metrics, self.trace.breakdown()
