"""Lower bounds on the device time of the window's launches, from the
work the program counts on each ``launch`` span and the chip's peaks.

The engine's Tracer puts on every ragged ``launch`` span (the ``tid`` is
the tier) its live ``tokens``, ``kv_read`` (the keys the live rows read
once per attention layer), ``kv_pairs`` (the query-key pairs attended)
and ``emitted`` (the rows whose logits are used).  The tier's
architecture (``bench/harness/arch/<name>.py``) counts a launch's bytes
and FLOPs from that work.  A launch's bound is the larger of its bytes
over HBM bandwidth and its FLOPs over the bf16 peak; a share of a
roofline is the bounds summed over the window's launches, over the
chip-seconds the device spent.  A speculative launch
counts neither its fused draft scan's tokens nor a verify row's extra
logit positions, so its bound is low there.
"""
from __future__ import annotations

import sys

from bench.harness import arch

WORK = ("tokens", "kv_read", "kv_pairs", "emitted")
_BYTES = {"bfloat16": 2, "float32": 4}


def launches(run):
    """``(tier, work)`` of each of the window's launches, or None when a
    launch carries no work counts (a program that does not count them, or
    a launch of another layout)."""
    out = []
    for e in run.phases:
        if e["name"] != "launch":
            continue
        args = e.get("args", {})
        if not all(k in args for k in WORK):
            return None
        out.append((e["tid"], args))
    return out or None


def counted(run) -> bool:
    """Whether every tier's attention is counted by ``kv_read`` and
    ``kv_pairs``: a sliding window reads fewer keys than they count, so a
    windowed tier is named on stderr and not counted at all."""
    for cfg in run.cfgs:
        windows = {layer.mixer.window for layer in cfg.layers
                   if layer.mixer.kind == "attn"}
        if windows - {None}:
            print(f"roofline: {cfg.name} has a sliding window "
                  f"{sorted(windows - {None})}; its keys are not counted",
                  file=sys.stderr, flush=True)
            return False
    return True


def _dtype_bytes(run) -> int:
    return _BYTES[run.cell.config["dtype"]]


def attention_bound_s(cfg, w, dtype_bytes: int, peak) -> float:
    """The attention kernel of one launch, every attention layer: the
    bytes it moves, and the FLOPs of ``kv_pairs`` query-key pairs."""
    a = arch.of(cfg)
    return max(a.attention_bytes(cfg, w, dtype_bytes) / peak.hbm_bytes_per_s,
               a.pair_flops(cfg) * w["kv_pairs"] / peak.bf16_flops)


def step_bound_s(cfg, w, dtype_bytes: int, peak) -> float:
    """The whole step of one launch: the weights it reads, the KV of
    ``kv_read`` positions read and of ``tokens`` written; two FLOPs per
    matrix weight per live token, the attention, and the output
    projection of the ``emitted`` rows."""
    a = arch.of(cfg)
    kv = a.kv_bytes_per_position(cfg, dtype_bytes)
    nbytes = a.weight_bytes_read(cfg, w, dtype_bytes) \
        + (w["kv_read"] + w["tokens"]) * kv
    flops = 2.0 * a.matmul_weights(cfg) * w["tokens"] \
        + a.pair_flops(cfg) * w["kv_pairs"] \
        + 2.0 * cfg.d_model * cfg.vocab_size * w["emitted"]
    return max(nbytes / peak.hbm_bytes_per_s, flops / peak.bf16_flops)


def share(run, bound, device_s: float):
    """The window's launch bounds summed, over ``device_s`` (seconds per
    chip) times the chips, in percent; None when nothing is counted."""
    work = launches(run)
    chips = run.trace.chips
    if work is None or not device_s or not chips or not counted(run):
        return None
    nbytes = _dtype_bytes(run)
    try:
        total = sum(bound(run.cfgs[tier], w, nbytes, run.peak)
                    for tier, w in work)
    except ValueError as e:             # layers the counts do not model
        print(f"roofline: {e}", file=sys.stderr, flush=True)
        return None
    return 100.0 * total / (device_s * chips)
