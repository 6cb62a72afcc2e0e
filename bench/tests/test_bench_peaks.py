"""The peak table and the model FLOPs that ``mfu`` divides by it."""
from __future__ import annotations

import pytest

from bench.harness import peaks
from repro.configs.base import get_config


def test_v5e_peaks_and_unknown_device():
    p = peaks.peak_for("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_per_s, p.hbm_bytes) == \
        (197e12, 819e9, 16e9)
    assert "TPU v5e" in p.source
    with pytest.raises(KeyError):
        peaks.peak_for("cpu")


@pytest.mark.parametrize("name", ["phi4-mini-3.8b", "starcoder2-7b"])
def test_weights_per_token_match_the_parameter_count(name):
    """Two FLOPs per matmul weight: every layer's projections plus the
    output head are the model's parameters less norms and (untied) the
    embedding lookup."""
    cfg = get_config(name)
    d, v = cfg.d_model, cfg.vocab_size
    norms = cfg.num_layers * 2 * d + d
    lookup = 0 if cfg.tie_embeddings else v * d
    weights = peaks.matmul_weights_per_layer(cfg) * cfg.num_layers + d * v
    assert weights == cfg.param_count() - norms - lookup
    assert peaks.token_flops(cfg, 1, True) == 2 * weights \
        + 4 * cfg.num_heads * cfg.head_dim * cfg.num_layers


def test_span_flops_is_the_sum_of_its_tokens():
    cfg = get_config("starcoder2-7b", "smoke")
    want = sum(peaks.token_flops(cfg, p + 1, p >= 17) for p in range(5, 20))
    assert peaks.span_flops(cfg, 5, 20, 3) == pytest.approx(want, rel=1e-12)
    assert peaks.span_flops(cfg, 7, 7, 0) == 0.0
