"""Reduce a device trace (``jax.profiler`` xplane) to the benchmark's
device numbers, with ``jax.profiler.ProfileData`` and nothing else.

* busy time per chip: the union of the intervals of the operations on
  the chip's op line, over the traced window; the idle share is one
  minus busy over the window;
* device time per kernel: the summed durations of the operations that
  are the kernel's custom calls;
* the longest idle stretches, each attributed to the engine phase
  (``Tracer``: admit / plan / launch / device_get / finish) the host was
  in at its middle.  The tracer's clock is put on the trace's by the
  launches: each ``run_ragged/<tier>`` annotation in the trace's host
  plane opens with the Tracer's ``launch`` phase of the same launch.
"""
from __future__ import annotations

import gzip
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

# a kernel is recognised by its name in the trace (the custom call's
# name, or the source of the op, in the event's name or stats)
KERNELS = {
    "ragged_attention": re.compile(r"ragged_attention|ragged_kernel"),
    "confidence_gate": re.compile(r"confidence_gate|gate_kernel"),
}
OP_LINES = ("XLA Ops",)
# control flow whose events span the ops of its body on the same line:
# counted for busy time, left out of the per-op times
CONTAINERS = ("while", "conditional", "call")
_TIER_PHASES = ("admit", "plan", "launch", "device_get", "finish")


@dataclass
class Summary:
    window_s: float
    chips: int = 0                           # chips with an op line
    busy_s: float = 0.0                      # mean over chips
    busy_by_chip: Dict[int, float] = field(default_factory=dict)
    kernel_s: Dict[str, float] = field(default_factory=dict)  # mean
    ops_s: Dict[str, float] = field(default_factory=dict)     # mean
    idle_by_phase: Dict[str, float] = field(default_factory=dict)

    def breakdown(self) -> dict:
        top = sorted(self.ops_s.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.idle_by_phase.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:                                  # pragma: no cover
        return {}


def op_key(name: str, stats: dict) -> str:
    """What an operation is, stable across compiles: a kernel's name, else
    the HLO instruction's name (the event is named by its HLO text,
    ``%fusion.150 = s32[16]... fusion(...)``) without the ``%`` and the
    instance number: ``fusion``, ``copy``, ``while``."""
    text = " ".join([name] + [str(v) for k, v in stats.items()
                              if k in ("tf_op", "long_name", "hlo_op")])
    for kernel, pat in KERNELS.items():
        if pat.search(text):
            return kernel
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.:]\d+$", "", head)


def union_length(iv: np.ndarray) -> float:
    """Total length of the union of ``[start, end)`` intervals."""
    if not len(iv):
        return 0.0
    iv = iv[np.argsort(iv[:, 0])]
    total, cur_s, cur_e = 0.0, iv[0, 0], iv[0, 1]
    for s, e in iv[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return total + cur_e - cur_s


def gaps(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The idle stretches ``[start, end)`` of ``[lo, hi)`` between the
    busy intervals."""
    if not len(iv):
        return np.array([[lo, hi]])
    iv = iv[np.argsort(iv[:, 0])]
    out, t = [], lo
    for s, e in iv:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return np.array([g for g in out if g[1] > g[0]]).reshape(-1, 2)


def _device_id(plane_name: str) -> Optional[int]:
    m = re.match(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def clock_offset_ns(host_launches: Dict[str, List[float]], phases,
                    tier_names: Sequence[str]) -> Optional[float]:
    """Trace time minus tracer time (ns), from matching the i-th
    ``run_ragged/<tier>`` annotation with the i-th ``launch`` phase of
    that tier (the median over all pairs)."""
    diffs = []
    for t, name in enumerate(tier_names):
        ann = sorted(host_launches.get(name, []))
        lau = sorted(e["ts"] * 1e3 for e in phases
                     if e["name"] == "launch" and e["tid"] == t)
        n = min(len(ann), len(lau))
        diffs += [a - b for a, b in zip(ann[:n], lau[:n])]
    return float(np.median(diffs)) if diffs else None


def _spans(phases, names, offset_ns):
    sel = sorted((e["ts"] * 1e3 + offset_ns, e["dur"] * 1e3, e["name"],
                  e["tid"]) for e in phases if e["name"] in names)
    arr = np.array([(s, s + d) for s, d, _, _ in sel]).reshape(-1, 2)
    return arr, [(n, t) for _, _, n, t in sel]


def _containing(spans: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Index of the span holding each instant (-1: none).  The host
    thread runs one phase at a time, so spans of one kind do not
    overlap."""
    if not len(spans):
        return np.full(len(t), -1)
    i = np.searchsorted(spans[:, 0], t, side="right") - 1
    ok = (i >= 0) & (t < spans[np.maximum(i, 0), 1])
    return np.where(ok, i, -1)


def attribute(gap_mid_ns: np.ndarray, phases, offset_ns: float,
              tier_names: Sequence[str]) -> List[str]:
    """The host phase at each gap's middle: ``<phase>/<tier>``, ``tick``
    (inside a tick but no phase), or ``between ticks``."""
    ph, ph_ids = _spans(phases, _TIER_PHASES, offset_ns)
    ticks, _ = _spans(phases, ("tick",), offset_ns)
    in_ph = _containing(ph, gap_mid_ns)
    in_tick = _containing(ticks, gap_mid_ns)
    out = []
    for p, k in zip(in_ph, in_tick):
        if p >= 0:
            name, tid = ph_ids[p]
            out.append(f"{name}/{tier_names[tid]}")
        else:
            out.append("tick" if k >= 0 else "between ticks")
    return out


def reduce(path: Optional[str], device_ids: Sequence[int], phases,
           tier_names: Sequence[str], window_s: float) -> Summary:
    """The summary of the trace at ``path`` over ``device_ids``."""
    summary = Summary(window_s=window_s)
    if path is None:
        return summary
    from jax.profiler import ProfileData
    if path.endswith(".gz"):              # a recorded trace, compressed
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    per_chip: Dict[int, np.ndarray] = {}
    ops: Dict[str, float] = defaultdict(float)
    host_launches: Dict[str, List[float]] = defaultdict(list)
    for plane in pd.planes:
        dev = _device_id(plane.name)
        if dev is None:
            if plane.name.startswith("/host"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith("run_ragged/"):
                            host_launches[ev.name.split("/", 1)[1]].append(
                                ev.start_ns)
            continue
        if dev not in device_ids:
            continue
        iv = []
        for line in plane.lines:
            if line.name not in OP_LINES:
                continue
            for ev in line.events:
                iv.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                key = op_key(ev.name, _stats(ev))
                if key not in CONTAINERS:
                    ops[key] += ev.duration_ns / 1e9
        per_chip[dev] = np.array(iv, np.float64).reshape(-1, 2)
    summary.chips = len(per_chip)
    n = max(len(per_chip), 1)
    summary.busy_by_chip = {d: union_length(iv) / 1e9
                            for d, iv in per_chip.items()}
    summary.busy_s = sum(summary.busy_by_chip.values()) / n
    summary.ops_s = {k: v / n for k, v in ops.items()}
    summary.kernel_s = {k: summary.ops_s[k] for k in KERNELS
                        if k in summary.ops_s}
    offset = clock_offset_ns(host_launches, phases, tier_names)
    if offset is not None and per_chip:
        lo = min(iv[:, 0].min() for iv in per_chip.values() if len(iv))
        hi = lo + window_s * 1e9
        idle: Dict[str, float] = defaultdict(float)
        for iv in per_chip.values():
            g = gaps(iv, lo, hi)
            labels = attribute((g[:, 0] + g[:, 1]) / 2, phases, offset,
                               tier_names)
            for (s, e), lab in zip(g, labels):
                idle[lab] += float(e - s) / 1e9 / n
        summary.idle_by_phase = dict(idle)
    return summary
