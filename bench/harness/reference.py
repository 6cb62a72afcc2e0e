"""Plain float32 reference of the dense GQA/RoPE decoder both tiers use.

Straight ``jax.numpy`` with ``default_matmul_precision("highest")``: no
kernel, cache, paging or batching, and nothing imported from the
program.  It reads the weights the benchmark made (``weights.py``) and
casts one layer's bfloat16 weights to float32 at a time, so it fits
beside them on a chip whose cache arenas have been freed.

It follows the model as the repo's blocks implement it
(``models/blocks.py``); each configuration file lists where that departs
from the published model (norms, biases, rotary fraction, rope scaling).

``precision="fp8"`` is the control: every matmul weight rounded to
float8 e4m3 with a scale per output channel, the step below bfloat16
that would tempt a later change.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

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


def _rope(x, pos, theta):
    """Rotate every pair (i, i + d/2) of the head dim by pos * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv            # [S, d/2]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "act",
                                   "theta", "eps", "precision"))
def _layer(x, lp, *, heads, kv_heads, head_dim, act, theta, eps, precision):
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        w = lambda name, ax=0: _weight(lp[name], precision, ax)  # noqa: E731
        pos = jnp.arange(s)
        h = _rmsnorm(x, lp["norm1"], eps)
        q = (h @ w("wq")).reshape(s, heads, head_dim)
        k = (h @ w("wk")).reshape(s, kv_heads, head_dim)
        v = (h @ w("wv")).reshape(s, kv_heads, head_dim)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        group = heads // kv_heads
        k = jnp.repeat(k, group, axis=1)                     # head h -> h // G
        v = jnp.repeat(v, group, axis=1)

        def attend(block):                # one block of PAD queries
            qb, qpos = block
            scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(head_dim)
            causal = qpos[:, None] >= pos[None, :]
            probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", probs, v)

        att = jax.lax.map(attend, (q.reshape(s // PAD, PAD, heads, head_dim),
                                   pos.reshape(s // PAD, PAD)))
        att = att.reshape(s, heads * head_dim)
        x = x + att @ w("wo")
        h = _rmsnorm(x, lp["norm2"], eps)
        if act == "swiglu":
            f = jax.nn.silu(h @ w("wi0")) * (h @ w("wi1"))
        elif act == "gelu":
            f = jax.nn.gelu(h @ w("wi"), approximate=True)
        else:
            raise ValueError(act)
        return x + f @ w("wo_ffn")


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
    sequence ``tokens`` (the logits there predict the token after)."""
    n = len(tokens)
    step = PAD if n <= 4 * PAD else 4 * PAD   # few lengths, few compiles
    s = -(-n // step) * step
    toks = np.zeros(s, np.int32)
    toks[:n] = tokens
    x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
    for i in range(cfg.num_periods):
        for b, layer in enumerate(cfg.period):
            blk = params["period"][f"block{b}"]
            lp = {"norm1": blk["norm1"][i], "norm2": blk["norm2"][i],
                  "wo_ffn": blk["ffn"]["wo"][i],
                  **{k: blk["mixer"][k][i] for k in ("wq", "wk", "wv", "wo")},
                  **{k: blk["ffn"][k][i] for k in ("wi", "wi0", "wi1")
                     if k in blk["ffn"]}}
            x = _layer(x, lp, heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                       head_dim=cfg.head_dim, act=layer.ffn.act,
                       theta=cfg.rope_theta, eps=cfg.norm_eps,
                       precision=precision)
    h = _final(x, jnp.asarray(rows, np.int32), params["final_norm"],
               eps=cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    v = cfg.vocab_size
    parts = []
    for a in range(0, v, VOCAB_BLOCK):
        b = min(a + VOCAB_BLOCK, v)
        w = head[a:b] if cfg.tie_embeddings else head[:, a:b]
        parts.append(_project(h, w, precision=precision,
                              rows_are_vocab=cfg.tie_embeddings))
    return jnp.concatenate(parts, axis=1)


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
