"""The dense GQA path of the harness gives, bit for bit, what it gave
before it moved behind ``bench/harness/arch/`` (commit 39a5cffe8679):
at the smoke cascade's size on the CPU and a fixed seed, the digest of
each tier's weights as ``weights.make`` lays them out, of the float32
reference's logits at fixed rows, and of ``score`` and the fp8
``control_score`` over a fixed answer.

The expected digests were read at that commit.  XLA's CPU backend sums
the starcoder2 smoke tier's matmuls in another order when the process
may use one core only, so each reading holds the value of a host with
several cores and, where it differs, that of one core; both were read
at that commit.
"""
from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import cells, reference, weights
from bench.tests import smoke_cell

SEED = 2**31 + 11
ROWS = [0, 17, 39]

PINNED = {
    "fast": {
        "weights": ("5c9d4055289f99a13105513811e5f6f8db80bad5537d591dab80932b74af9c04",),
        "logits": ("bca080936395585e65ec321256b4bf941e40d8e61f09bbf2be76a094d84c572d",),
        "control": ("62d25d475e852667276b3be434a234eb52f457967cd5a326ab8f258f90535b8c",),
        "score": ("a17d7e8cb22a4763bf8888b713b065a465b40282c717e7f555b785664814ac48",),
    },
    "expensive": {
        "weights": ("ccb95a8e90a35b2fa0f41d580457e0214eeb6a92587a94c76a53631a33ea4d22",),
        "logits": ("eb5386f6bd1d9444a0b181113a51dce29a1db55b2b82a2e9bf10bcf761b6807d",
                   "7d146c202ac47d81cd0abc4b76fb39bafed9226b6d9df2c8d8679001e73041fc"),
        "control": ("003c38aff4a260d7540aba9110d5817c5522e34cf88d4097fe602ea08c575ba8",
                    "89f84b3c2f0026620975f8057bcc4dfc3759e98c0c8f80288e7b883c64ba0785"),
        "score": ("d7aefc7e5c71a50606ab5a5231837b43562100b0c458a4fae4b85da0c1daa665",
                  "d1651dfe7fa799bea61adac8e1a4766e01471ba7b1c4b4bca3033a530333fe5f"),
    },
}


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("stream,tier", enumerate(PINNED))
def test_dense_weights_and_reference_are_unchanged(stream, tier):
    cfg = cells.tier_config(smoke_cell.CONFIG[tier])
    params = weights.make(cfg, SEED, stream, jnp.float32)
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    paths = [np.frombuffer(jax.tree_util.keystr(p).encode(), np.uint8)
             for p, _ in leaves]
    toks = np.random.default_rng(7).integers(0, cfg.vocab_size, 40)
    prompt, answer = toks[:25], toks[25:]
    control = reference.control_score(params, cfg, prompt, answer)
    score = reference.score(params, cfg, prompt, answer)
    got = {
        "weights": _digest(paths + [x for _, x in leaves]),
        "logits": _digest([reference.logits_at(params, cfg, toks, ROWS)]),
        "control": _digest([control[k] for k in sorted(control)]),
        "score": _digest([score[k] for k in sorted(score)]),
    }
    for k, want in PINNED[tier].items():
        assert got[k] in want, (tier, k, got[k])
