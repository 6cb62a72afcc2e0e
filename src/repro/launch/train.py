"""Training driver.

Runs real steps (CPU smoke scale or a real mesh): standard LM training or
LtC cascade training (Eq 4) of a fast arch against a frozen expensive
arch.

    PYTHONPATH=src python -m repro.launch.train \
        --arch gemma3-1b --variant smoke --steps 50 --batch 8 --seq 128
    PYTHONPATH=src python -m repro.launch.train \
        --arch gemma3-1b --expensive phi4-mini-3.8b --variant smoke ...
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save as save_ckpt
from repro.configs import get_config
from repro.data import Batches, bigram_lm
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import use_compile_cache
from repro.models import init_params


def run(arch: str, *, variant="smoke", steps=50, batch=8, seq=128,
        lr=1e-2, expensive=None, ltc_w=1.0, cost_c=0.5, seed=0,
        ckpt=None, exp_params=None, log_every=10, data_seed=0,
        return_losses=False, vocab=None, trigram_frac=0.3):
    cfg = get_config(arch, variant)
    key = jax.random.PRNGKey(seed)
    params = init_params(cfg, key, jnp.float32)

    tokens = bigram_lm(num_seqs=max(batch * 16, 256), seq_len=seq,
                       vocab=vocab or cfg.vocab_size, seed=data_seed,
                       trigram_frac=trigram_frac)
    it = iter(Batches({"tokens": tokens}, batch, seed=seed))

    extra = {}
    if cfg.frontend:
        extra["frontend_embeds"] = np.zeros(
            (batch, cfg.frontend_len, cfg.frontend_dim), np.float32)

    if expensive is None:
        train_step, opt = steps_lib.make_train_step(cfg, lr=lr)
        train_step = jax.jit(train_step)
        args_extra = ()
    else:
        exp_cfg = get_config(expensive, variant)
        if exp_params is None:
            exp_params = init_params(exp_cfg, jax.random.PRNGKey(seed + 1),
                                     jnp.float32)
        train_step, opt = steps_lib.make_ltc_train_step(
            cfg, exp_cfg, w=ltc_w, cost_c=cost_c, lr=lr)
        train_step = jax.jit(train_step)
        args_extra = (exp_params,)

    opt_state = opt.init(params)
    losses = []
    t0 = time.time()
    for i in range(steps):
        b = dict(next(it))
        b.update(extra)
        b = {k: jnp.asarray(v) for k, v in b.items()}
        params, opt_state, m = train_step(params, opt_state, *args_extra, b)
        losses.append(float(m["loss"] if "loss" in m else m["l_org"]))
        if log_every and (i + 1) % log_every == 0:
            print(f"step {i+1}: loss {losses[-1]:.4f} "
                  f"({(time.time()-t0)/(i+1):.2f}s/step)")
    if ckpt:
        save_ckpt(ckpt, params, step=steps)
        print(f"saved {ckpt}")
    if return_losses:
        return params, losses
    return params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--variant", default="smoke")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--expensive", default=None,
                    help="train with the LtC loss against this frozen arch")
    ap.add_argument("--ltc-w", type=float, default=1.0)
    ap.add_argument("--cost-c", type=float, default=0.5)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    use_compile_cache()
    run(args.arch, variant=args.variant, steps=args.steps, batch=args.batch,
        seq=args.seq, lr=args.lr, expensive=args.expensive, ltc_w=args.ltc_w,
        cost_c=args.cost_c, ckpt=args.ckpt)


if __name__ == "__main__":
    main()
