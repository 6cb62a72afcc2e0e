"""Dense GQA decoder: grouped-query attention with RoPE and a dense
feed-forward (SwiGLU or GELU) in every layer, all in the scanned period.

The parameter layout follows the program's (``repro.models.params``:
embedding, stacked ``period`` blocks, final norm, untied head); the
float32 forward pass follows the model as the repo's blocks implement it
(``models/blocks.py``), and each configuration file lists where that
departs from the published model (norms, biases, rotary fraction, rope
scaling).  The counts are those of the same layers, with no biases.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.reference import (PAD, VOCAB_BLOCK, _final, _project,
                                     _rmsnorm, _weight)

# published (Hugging Face config.json) key -> how the repo's ModelConfig
# states it; checked after the depth cut, so a file cannot claim widths
# the program does not run
HF_KEYS = {
    "hidden_size": lambda c: c.d_model,
    "intermediate_size": lambda c: c.period[0].ffn.d_ff,
    "num_attention_heads": lambda c: c.num_heads,
    "num_key_value_heads": lambda c: c.num_kv_heads,
    "head_dim": lambda c: c.head_dim,
    "num_hidden_layers": lambda c: c.num_layers,
    "vocab_size": lambda c: c.vocab_size,
    "tie_word_embeddings": lambda c: c.tie_embeddings,
    "rope_theta": lambda c: c.rope_theta,
    "rms_norm_eps": lambda c: c.norm_eps,
    "norm_epsilon": lambda c: c.norm_eps,
}


def layout(cfg) -> dict:
    """``{path: (shape, init)}`` of a dense GQA decoder whose layers all
    sit in the scanned period (``num_periods`` stacked copies of its
    blocks)."""
    if cfg.head or cfg.tail or cfg.frontend:
        raise ValueError(f"{cfg.name}: only scanned periods are laid out")
    d, v, n = cfg.d_model, cfg.vocab_size, cfg.num_periods
    hq, hkv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    out = {("embed",): ((v, d), "normal:0.02"),
           ("final_norm",): ((d,), "ones")}
    if not cfg.tie_embeddings:
        out[("lm_head",)] = ((d, v), "fan_in")
    for b, layer in enumerate(cfg.period):
        if layer.mixer.kind != "attn" or layer.ffn.kind != "dense":
            raise ValueError(f"{cfg.name}: dense attention layers expected")
        f, blk = layer.ffn.d_ff, ("period", f"block{b}")
        out[blk + ("norm1",)] = ((n, d), "ones")
        out[blk + ("norm2",)] = ((n, d), "ones")
        for name, shape in (("wq", (d, hq)), ("wk", (d, hkv)),
                            ("wv", (d, hkv)), ("wo", (hq, d))):
            out[blk + ("mixer", name)] = ((n,) + shape, "fan_in")
        if layer.ffn.act == "swiglu":
            ins = ("wi0", "wi1")
        elif layer.ffn.act == "gelu":
            ins = ("wi",)
        else:
            raise ValueError(f"{cfg.name}: feed-forward {layer.ffn.act!r}")
        for name in ins:
            out[blk + ("ffn", name)] = ((n, d, f), "fan_in")
        out[blk + ("ffn", "wo")] = ((n, f, d), "fan_in")
    return out


# --- the float32 forward pass ----------------------------------------------


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


# --- the counts --------------------------------------------------------------


def attention_layers(cfg) -> int:
    return sum(layer.mixer.kind == "attn" for layer in cfg.layers)


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


def matmul_weights(cfg) -> int:
    return cfg.num_layers * matmul_weights_per_layer(cfg)


def pair_flops(cfg) -> int:
    """Scores and weighted values: ``4 * heads * head_dim`` per
    query-key pair and attention layer."""
    return 4 * cfg.num_heads * cfg.head_dim * attention_layers(cfg)


def kv_bytes_per_position(cfg, dtype_bytes: int) -> int:
    """K and V of one position in every attention layer."""
    kv = 1 if cfg.kv_quant == "int8" else dtype_bytes
    return attention_layers(cfg) * cfg.num_kv_heads * cfg.head_dim * 2 * kv


def attention_bytes(cfg, work, dtype_bytes: int) -> int:
    """K and V of ``kv_read`` positions read, q read and o written for
    ``tokens``, in every attention layer."""
    return work["kv_read"] * kv_bytes_per_position(cfg, dtype_bytes) \
        + work["tokens"] * attention_layers(cfg) * cfg.num_heads \
        * cfg.head_dim * 2 * dtype_bytes


def weight_bytes(cfg, dtype_bytes: int) -> int:
    """The weights every launch reads whole: the head (the embedding when
    tied), the layers' matrices and norms, the final norm."""
    d, v = cfg.d_model, cfg.vocab_size
    n = v * d + d
    n += cfg.num_layers * (matmul_weights_per_layer(cfg) + 2 * d)
    return n * dtype_bytes


def weight_bytes_read(cfg, work, dtype_bytes: int) -> int:
    """Every weight read whole, and an untied embedding's rows of
    ``tokens``."""
    rows = 0 if cfg.tie_embeddings else work["tokens"] * cfg.d_model \
        * dtype_bytes
    return weight_bytes(cfg, dtype_bytes) + rows
