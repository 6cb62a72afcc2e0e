"""Published peaks per device, and the model FLOPs the benchmark counts.

Peaks are keyed by ``jax.Device.device_kind``.  A device that is not in
the table is an error, never a default: a utilization against a guessed
peak is no measurement.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    bf16_flops: float        # FLOP/s, dense bf16 matmul
    hbm_bytes_per_s: float   # HBM bandwidth
    hbm_bytes: float         # HBM capacity
    source: str


# Google Cloud documentation, "TPU v5e" (system architecture page):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
_V5E = Peak(197e12, 819e9, 16e9, "Google Cloud documentation, TPU v5e")

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def matmul_weights_per_layer(cfg) -> int:
    """Weights one token multiplies through in one decoder layer of a
    dense GQA model (attention projections and the feed-forward), as the
    repo's blocks lay them out: no biases."""
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    attn = d * h * hd * 2 + d * kv * hd * 2          # wq, wo, wk, wv
    total = 0
    for layer in cfg.layers:
        f = layer.ffn
        if layer.mixer.kind != "attn" or f.kind != "dense":
            raise ValueError(f"{cfg.name}: only dense attention layers are "
                             "counted here")
        total += attn + (3 if f.act == "swiglu" else 2) * d * f.d_ff
    return total // cfg.num_layers


def token_flops(cfg, context: int, emits: bool) -> float:
    """Model FLOPs of one token at 0-based position ``context - 1``: two
    per weight it multiplies through in every layer, the attention
    scores and weighted values over its ``context`` keys
    (``4 * heads * head_dim`` per key and layer), and the output
    projection when its logits are used (``emits``)."""
    per_layer = 2 * matmul_weights_per_layer(cfg) \
        + 4 * cfg.num_heads * cfg.head_dim * context
    head = 2 * cfg.d_model * cfg.vocab_size if emits else 0
    return float(cfg.num_layers * per_layer + head)


def span_flops(cfg, start: int, stop: int, emitted: int) -> float:
    """Model FLOPs of positions ``[start, stop)`` of one sequence on one
    tier, ``emitted`` of which had their logits used: the closed form of
    summing :func:`token_flops` over the span."""
    n = stop - start
    if n <= 0:
        return 0.0
    keys = (stop * (stop + 1) - start * (start + 1)) // 2   # sum of p + 1
    per_layer = 2 * matmul_weights_per_layer(cfg) * n \
        + 4 * cfg.num_heads * cfg.head_dim * keys
    return float(cfg.num_layers * per_layer
                 + 2 * cfg.d_model * cfg.vocab_size * emitted)
