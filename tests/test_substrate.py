"""Substrate tests: optimizers, data pipeline, checkpointing, classifier."""
import os
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._hyp_compat import given, settings, st  # hypothesis or skip-stub

from repro import checkpoint as ckpt
from repro.data import Batches, bigram_lm
from repro.data.synthetic import teacher_task
from repro.optim import adafactor, adamw, cosine, sgd_momentum, step_decay


def _quad_problem():
    target = jnp.array([1.0, -2.0, 3.0])

    def loss(p):
        return jnp.sum((p["x"] - target) ** 2) + jnp.sum(p["w"] ** 2)

    params = {"x": jnp.zeros(3), "w": jnp.ones((2, 2))}
    return loss, params, target


@pytest.mark.parametrize("opt_fn,lr,steps,tol", [
    (lambda: sgd_momentum(momentum=0.9), 0.05, 200, 0.05),
    (lambda: adamw(), 0.1, 200, 0.05),
    (lambda: adafactor(), 0.5, 400, 0.3),   # no momentum; sqrt-decayed lr
])
def test_optimizers_converge(opt_fn, lr, steps, tol):
    loss, params, target = _quad_problem()
    opt = opt_fn()
    state = opt.init(params)
    g = jax.jit(jax.grad(loss))
    for t in range(steps):
        lr_t = lr / np.sqrt(t + 1) if opt.name == "adafactor" else lr
        params, state = opt.update(params, g(params), state, lr_t)
    np.testing.assert_allclose(params["x"], target, atol=tol)
    np.testing.assert_allclose(params["w"], 0.0, atol=tol)


def test_adafactor_state_is_factored():
    opt = adafactor()
    params = {"big": jnp.zeros((64, 32)), "vec": jnp.zeros((7,))}
    state = opt.init(params)
    assert state["v"]["big"]["vr"].shape == (64,)
    assert state["v"]["big"]["vc"].shape == (32,)
    assert state["v"]["vec"]["v"].shape == (7,)


def test_schedules():
    s = step_decay(0.1, [10, 20], 0.2)
    assert float(s(5)) == pytest.approx(0.1)
    assert float(s(15)) == pytest.approx(0.02)
    assert float(s(25)) == pytest.approx(0.004)
    c = cosine(1.0, 100, warmup=10)
    assert float(c(0)) == pytest.approx(0.0)
    assert float(c(10)) == pytest.approx(1.0, abs=0.02)
    assert float(c(100)) == pytest.approx(0.1, abs=0.02)


def test_batches_cover_epoch():
    x = np.arange(100)
    b = Batches({"x": x}, 10, seed=0)
    seen = np.concatenate([bb["x"] for bb in b.epoch()])
    assert sorted(seen.tolist()) == list(range(100))


def test_bigram_lm_has_learnable_structure():
    toks = bigram_lm(num_seqs=200, seq_len=64, vocab=64, branching=2,
                     trigram_frac=0.0, seed=0)
    # with branching=2, each token has <=2 successors
    succ = {}
    for row in toks:
        for a, b in zip(row[:-1], row[1:]):
            succ.setdefault(int(a), set()).add(int(b))
    assert max(len(v) for v in succ.values()) <= 2


@pytest.mark.parametrize("vocab", [64, 262144])
def test_bigram_lm_trigram_successor_is_a_function(vocab):
    """With only trigram steps, each token is fixed by the two before it
    — from a dense table at small vocabularies, from a hash at LLM ones
    (where the table would not fit in memory)."""
    toks = bigram_lm(num_seqs=64, seq_len=32, vocab=vocab,
                     trigram_frac=1.0, seed=3)
    assert toks.shape == (64, 32) and toks.dtype == np.int32
    assert 0 <= toks.min() and toks.max() < vocab
    nxt = {}
    for row in toks:
        for a, b, c in zip(row[:-2], row[1:-1], row[2:]):
            assert nxt.setdefault((int(a), int(b)), int(c)) == int(c)
    np.testing.assert_array_equal(
        toks, bigram_lm(num_seqs=64, seq_len=32, vocab=vocab,
                        trigram_frac=1.0, seed=3))


def test_teacher_task_capacity_headroom():
    ds, info = teacher_task(num_samples=2000, return_info=True)
    assert 0.5 < info["bayes_acc"] <= 1.0
    assert ds.x.shape[0] == 2000
    tr, va, te = ds.split((0.8, 0.1, 0.1))
    assert abs(tr.x.shape[0] - 1600) <= 2


def test_checkpoint_roundtrip():
    tree = {"a": {"b": jnp.arange(6).reshape(2, 3).astype(jnp.float32)},
            "c": [jnp.ones(4), jnp.zeros((2, 2))]}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ck.npz")
        ckpt.save(path, tree, step=7)
        back = ckpt.load(path, like=tree)
        jax.tree.map(lambda x, y: np.testing.assert_array_equal(x, y),
                     tree, back)


def test_checkpoint_missing_key_raises():
    tree = {"a": jnp.ones(3)}
    bigger = {"a": jnp.ones(3), "b": jnp.ones(2)}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ck.npz")
        ckpt.save(path, tree)
        with pytest.raises(KeyError):
            ckpt.load(path, like=bigger)


@pytest.mark.parametrize("env", ["/elsewhere/jax-cache", None])
def test_compile_cache_dir(monkeypatch, env):
    """An exported JAX_COMPILATION_CACHE_DIR is left to JAX; otherwise the
    cache goes to the fixed `.jax_cache` at the root of the checkout."""
    from repro.launch import compile_cache
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    before = jax.config.jax_compilation_cache_dir
    try:
        used = compile_cache.use_compile_cache()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if env is None:
        assert used == after == str(compile_cache.REPO_CACHE_DIR)
        assert compile_cache.REPO_CACHE_DIR.parent == \
            Path(__file__).resolve().parents[1]
    else:
        assert used == env and after == before
