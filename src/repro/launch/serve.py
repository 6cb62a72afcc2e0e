"""Cascade serving: batched requests through a fast LLM, escalation of
low-confidence sequences to an expensive LLM (the paper's system, Fig 1,
with LLMs as the members).

:func:`serve_cascade` is a thin compatibility wrapper: the decode loop is
driven by :class:`repro.serving.CascadeEngine` (continuous batching over
KV slot pools, per-request gating, packed escalation queues).  Flow per
request:

  1. fast tier: prefill prompt -> greedy decode `gen_len` tokens, per-token
     confidence from the fused gate (max softmax prob — the paper's conf).
  2. sequence confidence = aggregate of token confs (mean by default).
  3. sequences with conf <= δ are escalated: the expensive tier re-decodes
     them as dense packed sub-batches; Eq 7 cost accounting uses
     per-member FLOPs/token with N^exp = #escalated.

For request-level asynchronous serving (Poisson arrivals, latency
percentiles, escalation budgets) use ``repro.launch.serve_async``.
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import confidence as conf_lib
from repro.data import bigram_lm
from repro.kernels import ops as kernel_ops
from repro.launch.compile_cache import use_compile_cache
from repro.models import init_cache, init_params, transformer
from repro.serving import CascadeEngine, TierSpec
from repro.serving.engine import VirtualClock


@dataclass
class ServeStats:
    n: int
    n_exp: int
    flops_fast: float
    flops_exp: float

    @property
    def flops_cascade(self) -> float:
        """Eq 7 with FLOPs in place of MACs."""
        return self.flops_fast + (self.n_exp / max(self.n, 1)) * self.flops_exp


def greedy_decode(cfg, params, prompts, gen_len, *, use_gate_kernel=False):
    """prompts [B, P] int32.  Returns (tokens [B, gen_len], conf [B, gen_len])."""
    B, P = prompts.shape
    total = P + gen_len
    cache = init_cache(cfg, B, total, jnp.float32)

    batch = {"tokens": prompts}
    if cfg.frontend:
        batch["frontend_embeds"] = jnp.zeros(
            (B, cfg.frontend_len, cfg.frontend_dim), jnp.float32)
    logits, part_cache, _ = transformer.forward(params, cfg, batch,
                                                mode="prefill")

    def put(full, part):
        if full.shape == part.shape:
            return part.astype(full.dtype)
        return full.at[tuple(slice(0, s) for s in part.shape)].set(
            part.astype(full.dtype))

    cache = jax.tree.map(put, cache, part_cache)

    @jax.jit
    def step(tok, cache, pos):
        lg, new_cache = transformer.decode_step(params, cfg, tok, cache, pos)
        if use_gate_kernel:
            gate = kernel_ops.confidence_gate(lg[:, 0])
            nxt = gate["argmax"][:, None]
            c = gate["conf"]
        else:
            nxt = jnp.argmax(lg[:, -1], -1)[:, None]
            c = conf_lib.max_prob(lg[:, -1])
        return nxt.astype(jnp.int32), c, new_cache

    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    confs, toks = [], []
    first_conf = conf_lib.max_prob(logits[:, -1])
    for t in range(gen_len):
        toks.append(tok)
        confs.append(first_conf if t == 0 else c)  # conf of the token emitted
        pos = jnp.full((B, 1), P + t, jnp.int32)
        tok, c, cache = step(tok, cache, pos)
    return jnp.concatenate(toks, 1), jnp.stack(confs, 1)


def serve_cascade(fast_arch="gemma3-1b", exp_arch="phi4-mini-3.8b", *,
                  variant="smoke", fast_variant=None, exp_variant=None,
                  batch=8, prompt_len=32, gen_len=16,
                  delta=0.5, seed=0, fast_params=None, exp_params=None,
                  use_gate_kernel=False, pack=False, verbose=True,
                  slots=None):
    """Compatibility wrapper over :class:`repro.serving.CascadeEngine`.

    All `batch` requests arrive at t=0 and are drained to completion;
    returns the old contract ``(out_tokens [B,G], seq_conf [B],
    ServeStats)``.  ``pack`` is accepted for backwards compatibility —
    the engine always packs escalations densely.  ``slots`` bounds the
    per-tier KV slot pools (default: `batch`, i.e. the old synchronous
    behaviour; smaller values exercise continuous batching).
    """
    del pack  # escalation is always packed by the engine
    fast_cfg = get_config(fast_arch,
                          variant if fast_variant is None else fast_variant)
    exp_cfg = get_config(exp_arch,
                         variant if exp_variant is None else exp_variant)
    vocab = min(fast_cfg.vocab_size, exp_cfg.vocab_size)

    key = jax.random.PRNGKey(seed)
    if fast_params is None:
        fast_params = init_params(fast_cfg, key, jnp.float32)
    if exp_params is None:
        exp_params = init_params(exp_cfg, jax.random.PRNGKey(seed + 1),
                                 jnp.float32)

    prompts = np.asarray(bigram_lm(num_seqs=batch, seq_len=prompt_len,
                                   vocab=vocab, seed=seed))

    t0 = time.time()
    engine = CascadeEngine(
        [TierSpec("fast", fast_cfg, fast_params),
         TierSpec("exp", exp_cfg, exp_params)],
        slots=batch if slots is None else slots,
        prompt_len=prompt_len, gen_len=gen_len, deltas=[delta],
        use_gate_kernel=use_gate_kernel, clock=VirtualClock())
    for p in prompts:
        engine.submit(p, arrival_time=0.0)
    engine.run()

    out_tokens = np.stack([np.asarray(r.tokens, np.int32)
                           for r in engine.requests])
    seq_conf = np.asarray([r.seq_conf_by_tier[0] for r in engine.requests],
                          np.float32)
    n_exp = engine.scheduler.gate_stats[0].escalated

    # Eq 7 accounting: FLOPs per generated token = 2 * active params
    flops_fast = 2.0 * fast_cfg.active_param_count() * gen_len
    flops_exp = 2.0 * exp_cfg.active_param_count() * gen_len
    stats = ServeStats(n=batch, n_exp=n_exp, flops_fast=flops_fast,
                       flops_exp=flops_exp)
    if verbose:
        print(f"served {batch} requests in {time.time()-t0:.1f}s: "
              f"escalated {n_exp}/{batch} (δ={delta})")
        print(f"  FLOPs/token: fast={flops_fast/gen_len:.3e} "
              f"exp={flops_exp/gen_len:.3e} "
              f"cascade={stats.flops_cascade/gen_len:.3e}")
    return jnp.asarray(out_tokens), jnp.asarray(seq_conf), stats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", default="gemma3-1b")
    ap.add_argument("--expensive", default="phi4-mini-3.8b")
    ap.add_argument("--variant", default="smoke")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--delta", type=float, default=0.5)
    ap.add_argument("--gate-kernel", action="store_true",
                    help="use the Pallas confidence_gate (interpret on CPU)")
    ap.add_argument("--pack", action="store_true",
                    help="(compat flag; the engine always packs)")
    ap.add_argument("--slots", type=int, default=None,
                    help="per-tier KV slot pool size (default: batch)")
    args = ap.parse_args()
    use_compile_cache()
    serve_cascade(args.fast, args.expensive, variant=args.variant,
                  batch=args.batch, prompt_len=args.prompt_len,
                  gen_len=args.gen_len, delta=args.delta,
                  use_gate_kernel=args.gate_kernel, pack=args.pack,
                  slots=args.slots)


if __name__ == "__main__":
    main()
