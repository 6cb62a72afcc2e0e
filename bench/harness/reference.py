"""Plain float32 reference of the tiers, around each architecture's
forward pass (``bench/harness/arch/<name>.py``).

Straight ``jax.numpy`` with ``default_matmul_precision("highest")``: no
kernel, cache, paging or batching, and nothing imported from the
program.  It reads the weights the benchmark made (``weights.py``); an
architecture's forward pass casts one layer's bfloat16 weights to
float32 at a time, so it fits beside them on a chip whose cache arenas
have been freed.  This module holds what every architecture shares: the
weight cast, the norm, the final norm and the output projection in
blocks of the vocabulary, and the scores of a served answer.

``precision="fp8"`` is the control: every matmul weight rounded to
float8 e4m3 with a scale per output channel, the step below bfloat16
that would tempt a later change.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import arch

PAD = 256            # sequences are padded to a multiple of this
VOCAB_BLOCK = 20000  # output-projection rows cast to float32 at a time

_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


def _weight(w, precision: str, in_axis: int):
    """A bfloat16 weight in float32, or through fp8 for the control
    (one scale per output channel: the max over the input axis)."""
    w = w.astype(jnp.float32)
    if precision == "f32":
        return w
    if precision != "fp8":
        raise ValueError(precision)
    s = jnp.max(jnp.abs(w), axis=in_axis, keepdims=True) / _F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(_F8).astype(jnp.float32) * s


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


@partial(jax.jit, static_argnames=("eps",))
def _final(x, rows, scale, *, eps):
    return _rmsnorm(x[rows], scale, eps)


@partial(jax.jit, static_argnames=("precision", "rows_are_vocab"))
def _project(h, w, *, precision, rows_are_vocab):
    with jax.default_matmul_precision("highest"):
        if rows_are_vocab:                    # tied embedding [V, d]
            return h @ _weight(w, precision, 1).T
        return h @ _weight(w, precision, 0)   # head [d, V]


def logits_at(params, cfg, tokens, rows, precision: str = "f32"):
    """Float32 logits ``[len(rows), V]`` at positions ``rows`` of the
    sequence ``tokens``, by the forward pass of the tier's architecture
    (``bench/harness/arch/``)."""
    return arch.of(cfg).logits_at(params, cfg, tokens, rows, precision)


@jax.jit
def _summary(logits, served):
    """Per row: the best logit, the served token's logit, the argmax,
    and the max softmax probability (the gate's confidence)."""
    best = jnp.max(logits, axis=1)
    at = jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0]
    conf = 1.0 / jnp.sum(jnp.exp(logits - best[:, None]), axis=1)
    return best, at, jnp.argmax(logits, axis=1), conf


def _sequence(prompt, answer):
    prompt = np.asarray(prompt, np.int64)
    answer = np.asarray(answer, np.int64)
    seq = np.concatenate([prompt, answer[:-1]])
    return seq, np.arange(len(prompt) - 1, len(seq)), answer


def score(params, cfg, prompt, answer, precision: str = "f32") -> dict:
    """The reference over ``prompt`` + ``answer``: for each answer token,
    the best logit, the answer token's logit, the argmax and the
    confidence at the position that predicted it."""
    seq, rows, answer = _sequence(prompt, answer)
    lg = logits_at(params, cfg, seq, rows, precision)
    best, at, arg, conf = jax.device_get(
        _summary(lg, jnp.asarray(answer, np.int32)))
    return {"best": np.asarray(best, np.float64),
            "at": np.asarray(at, np.float64),
            "argmax": np.asarray(arg), "conf": np.asarray(conf, np.float64)}


def control_score(params, cfg, prompt, answer) -> dict:
    """The fp8 control at the same positions: the reference's best logit
    and confidence, and, for the token fp8 puts first, the reference's
    logit (``at``) and fp8's own confidence (``conf_low``)."""
    seq, rows, answer = _sequence(prompt, answer)
    lg = logits_at(params, cfg, seq, rows, "f32")
    low = logits_at(params, cfg, seq, rows, "fp8")
    _, _, low_arg, low_conf = _summary(low, jnp.asarray(answer, np.int32))
    best, at, _, conf = jax.device_get(_summary(lg, low_arg))
    return {"best": np.asarray(best, np.float64),
            "at": np.asarray(at, np.float64),
            "conf": np.asarray(conf, np.float64),
            "conf_low": np.asarray(jax.device_get(low_conf), np.float64)}
