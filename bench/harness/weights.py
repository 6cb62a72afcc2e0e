"""Tier weights made on the device from the run's seed.

The benchmark makes the weights and hands them to the program, so that
the reference can read them without reading anything the program made.
The pytree follows the program's parameter layout for dense GQA decoder
stacks (``repro.models.params``: embedding, stacked ``period`` blocks,
final norm, untied head); the scales are the program's own init
(``normal(0.02)`` embedding, ``1/sqrt(fan_in)`` matrices, unit norms),
so the logits have the scale the served path is used to.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


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


def _scale(shape, init) -> float:
    if init.startswith("normal:"):
        return float(init.split(":")[1])
    return 1.0 / math.sqrt(shape[-2])       # fan in of a (stacked) matrix


def root_key(seed: int, stream: int):
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    hi, lo = np.random.default_rng([seed, stream]).integers(
        0, 2**31 - 1, size=2)
    return jax.random.fold_in(jax.random.PRNGKey(int(lo)), int(hi))


def make(cfg, seed: int, stream: int, dtype) -> dict:
    """The tier's params, made in ``dtype`` by one jitted program."""
    spec = layout(cfg)

    def build(key):
        flat = {}
        for i, (path, (shape, init)) in enumerate(sorted(spec.items())):
            if init == "ones":
                flat[path] = jnp.ones(shape, dtype)
                continue
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            flat[path] = (x * _scale(shape, init)).astype(dtype)
        tree: dict = {}
        for path, x in flat.items():
            node = tree
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = x
        return tree

    return jax.jit(build)(root_key(seed, stream))
