"""Tier weights made on the device from the run's seed.

The benchmark makes the weights and hands them to the program, so that
the reference can read them without reading anything the program made.
The pytree is the layout of the tier's architecture
(``bench/harness/arch/<name>.py``), which follows the program's
parameters; the scales are the program's own init (``normal(0.02)``
embedding, ``1/sqrt(fan_in)`` matrices, unit norms), so the logits have
the scale the served path is used to.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import arch


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
    spec = arch.of(cfg).layout(cfg)

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
