"""Ragged flat token-batch execution: the ragged-attention kernel vs its
jnp oracle over arbitrary per-row q_len in [0, C], the flat work-list
layout, and engine-level three-way parity — **bit-identical token
streams and escalation decisions** across the ragged flat executor, the
padded mixed executor, and the legacy split executor — over uniform,
lognormal, over-subscribed, preemption, and prefix-cache workloads,
single-device and on 8 simulated sharded devices.

Also asserts the compiled-program discipline the bucketed flat widths
exist for: warmup compiles every bucket, and no tick launches a width
outside the warmed set (zero mid-run recompiles across a mixed-length
run, where the legacy unified path paid a chunk-width AND a width-1
compile).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kernel_ops
from repro.kernels import ref
from repro.kernels.ragged_attention import flat_work_layout
from repro.serving import CascadeEngine, CascadeScheduler, TierSpec  # noqa: F401
from repro.serving.engine import VirtualClock
from repro.serving.request import RequestState

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# kernel vs jnp oracle
# ---------------------------------------------------------------------------


def _flat_case(rng, B, C, KV, G, hd, P, bs, qlens, quant=False,
               window=None, q_start=None):
    """Build a flat-packed batch + pool and return (kernel, oracle).
    The page table is a shuffled, non-contiguous draw of blocks; each
    row's first query position is drawn unless ``q_start`` gives it."""
    N = B * P + 1
    if quant:
        kp = jnp.asarray(rng.integers(-127, 128, (N, bs, KV, hd)), jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, (N, bs, KV, hd)), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.05, (N, bs, KV)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.05, (N, bs, KV)), jnp.float32)
    else:
        kp = jnp.asarray(rng.standard_normal((N, bs, KV, hd)), jnp.float32)
        vp = jnp.asarray(rng.standard_normal((N, bs, KV, hd)), jnp.float32)
        ks = vs = None
    pt = jnp.asarray(
        rng.permutation(np.arange(1, N))[:B * P].reshape(B, P), jnp.int32)
    q_len = np.asarray(qlens, np.int32)
    if q_start is None:
        q_start = [int(rng.integers(0, P * bs - C)) for _ in range(B)]
    q_start = np.asarray(q_start, np.int32)
    q_rows = rng.standard_normal((B, C, KV, G, hd)).astype(np.float32)
    total = int(q_len.sum())
    W = max(8, 1 << (max(total, 1) - 1).bit_length())
    flat = np.zeros((W, KV, G, hd), np.float32)
    o = 0
    for b in range(B):
        n = int(q_len[b])
        flat[o:o + n] = q_rows[b, :n]
        o += n
    args = (jnp.asarray(flat), kp, vp, pt, jnp.asarray(q_start),
            jnp.asarray(q_len))
    kw = dict(k_scale=ks, v_scale=vs, window=window)
    got = kernel_ops.ragged_attention(*args, interpret=True, **kw)
    want = ref.ragged_attention_ref(*args, **kw)
    return np.asarray(got), np.asarray(want), total


# Shapes of the oracle cases: the base batch, then cases for the page
# groups (``ppg = 128 // bs`` pages, clamped to P): rows longer than two
# groups, contexts ending mid-group and on a group edge, starcoder2's
# head layout, and a prefill row spanning several tiles beside decode.
BASE = dict(B=8, C=16, KV=2, G=2, hd=32, P=5, bs=16)
LONG = dict(BASE, B=4, P=20)                    # 3 groups of 8 pages


@pytest.mark.parametrize("qlens,shape", [
    ([3, 0, 16, 1, 1, 7, 0, 5], {}),      # arbitrary mix incl. stalls
    ([1] * 8, {}),                        # decode-only tick
    ([16] * 8, {}),                       # full prefill tick
    ([0] * 8, {}),                        # all rows idle
    ([16, 0, 0, 0, 0, 0, 0, 0], {}),      # single live row
    ([8, 8, 0, 0, 0, 0, 0, 0], {}),       # total exactly a bucket boundary
    ([1, 16, 3, 1], LONG),
    # last keys 127, 128, 200 and 127: on a group edge, one past it,
    # mid-group, and a 16-query tile ending on the edge
    ([1, 1, 1, 16], dict(LONG, q_start=[127, 128, 200, 112])),
    ([1, 5, 0, 1, 2, 1, 1, 1], dict(BASE, KV=4, G=9)),
    ([40, 1, 1, 1], dict(LONG, C=48, q_start=[200, 40, 300, 7])),
], ids=[f"qlens{i}" for i in range(6)] + [
    "rows_past_two_groups", "contexts_at_group_edges",
    "starcoder2_heads", "prefill_across_tiles_with_decode"])
def test_ragged_kernel_matches_oracle(qlens, shape):
    """Rows with ANY q_len in [0, C] pack into one flat batch; outputs
    match the jnp oracle per token, and padding slots are exact zero."""
    rng = np.random.default_rng(0)
    got, want, total = _flat_case(rng, **{**BASE, **shape}, qlens=qlens)
    np.testing.assert_allclose(got[:total], want[:total],
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(got[total:], 0.0)


@pytest.mark.parametrize("quant,window,shape", [
    (True, None, BASE), (False, 24, BASE), (True, 16, BASE),
    (False, 200, LONG),                   # window opens mid-group
    (True, 100, LONG),
], ids=["True-None", "False-24", "True-16", "window_mid_group",
        "int8_window_long_rows"])
def test_ragged_kernel_int8_and_window(quant, window, shape):
    rng = np.random.default_rng(7)
    qlens = rng.integers(0, 17, shape["B"])
    got, want, total = _flat_case(rng, **shape, qlens=qlens, quant=quant,
                                  window=window)
    np.testing.assert_allclose(got[:total], want[:total],
                               rtol=1e-4, atol=1e-5)


def test_ragged_kernel_odd_shapes():
    rng = np.random.default_rng(3)
    got, want, total = _flat_case(rng, B=3, C=5, KV=1, G=4, hd=16,
                                  P=3, bs=8, qlens=[5, 2, 4])
    np.testing.assert_allclose(got[:total], want[:total],
                               rtol=2e-5, atol=2e-6)


def test_flat_work_layout_covers_every_tile_once():
    """The static work list (length num_tiles + B) assigns every flat
    tile a contiguous span of owning rows in tile-major order, with
    first/last flags bracketing each tile's span — the invariant the
    kernel's accumulator init/finalize depends on."""
    rng = np.random.default_rng(5)
    for _ in range(50):
        B = int(rng.integers(1, 9))
        nt = int(rng.integers(1, 9))
        TQ = 16
        q_len = rng.integers(0, 33, B).astype(np.int32)
        while q_len.sum() > nt * TQ:
            q_len[rng.integers(B)] = 0
        wt, wr, wf, wl, rs = (np.asarray(a) for a in flat_work_layout(
            jnp.asarray(q_len), nt, TQ))
        assert wt.shape == (nt + B,)
        # tile-major sorted, every tile present at least once
        assert (np.diff(wt) >= 0).all()
        assert set(wt.tolist()) == set(range(nt))
        # per tile: exactly one first and one last flag
        for t in range(nt):
            span = np.where(wt == t)[0]
            assert wf[span].sum() == 1 and wf[span[0]] == 1
            assert wl[span].sum() == 1 and wl[span[-1]] == 1
        # every live row appears on each tile its token range intersects
        starts = np.concatenate([[0], np.cumsum(q_len)])[:B]
        for b in range(B):
            if q_len[b] == 0:
                continue
            lo, hi = starts[b], starts[b] + q_len[b]
            tiles = {t for t in range(nt)
                     if lo < (t + 1) * TQ and hi > t * TQ}
            got = {int(t) for t, r in zip(wt, wr) if r == b}
            assert got == tiles, (b, q_len, got, tiles)


# ---------------------------------------------------------------------------
# engine: ragged vs padded vs split three-way parity
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_parts():
    from repro.configs import get_config
    from repro.models import init_params
    cfg = get_config("gemma3-1b", "smoke")
    fast_p = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    exp_p = init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    return cfg, fast_p, exp_p


def _mk(cfg, fast_p, exp_p, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("prompt_len", 16)
    kw.setdefault("gen_len", 4)
    kw.setdefault("deltas", [0.5])
    kw.setdefault("kv_block_size", 4)
    kw.setdefault("prefill_chunk", 5)
    kw.setdefault("clock", VirtualClock())
    return CascadeEngine([TierSpec("fast", cfg, fast_p),
                          TierSpec("exp", cfg, exp_p)], **kw)


def _drain(eng, prompts, arrivals=None):
    eng.warmup()
    for i, p in enumerate(prompts):
        t = 0.0 if arrivals is None else float(arrivals[i])
        eng.submit(p, arrival_time=t)
    eng.run(max_steps=1000)
    assert all(r.state is RequestState.DONE for r in eng.requests)
    return eng


def _check_streams(a_eng, b_eng):
    for a, b in zip(a_eng.requests, b_eng.requests):
        assert a.tokens == b.tokens, (a.rid, a.tokens, b.tokens)
        assert a.tier == b.tier
        np.testing.assert_allclose(a.token_conf, b.token_conf, rtol=1e-5)


def _pick_delta(cfg, fast_p, exp_p, prompts, **kw):
    """Probe tier-0 confidences (no escalation) and return a δ in the
    widest gap, so the gate genuinely splits the batch."""
    probe = _drain(_mk(cfg, fast_p, exp_p, deltas=[0.0], **kw), prompts)
    confs = sorted(r.seq_conf_by_tier[0] for r in probe.requests)
    gaps = np.diff(confs)
    i = int(np.argmax(gaps))
    return float((confs[i] + confs[i + 1]) / 2)


def _three_way(cfg, fast_p, exp_p, prompts, arrivals=None, **kw):
    rag = _drain(_mk(cfg, fast_p, exp_p, **kw), prompts, arrivals)
    assert rag.ragged_step and all(rt.ragged for rt in rag.runtimes)
    pad = _drain(_mk(cfg, fast_p, exp_p, use_ragged_step=False, **kw),
                 prompts, arrivals)
    assert not pad.ragged_step and all(rt.unified and not rt.ragged
                                       for rt in pad.runtimes)
    spl = _drain(_mk(cfg, fast_p, exp_p, use_unified_step=False, **kw),
                 prompts, arrivals)
    _check_streams(rag, pad)
    _check_streams(rag, spl)
    return rag, pad, spl


def test_ragged_matches_padded_and_split_mixed_lengths(tiny_parts):
    """Acceptance: the flat executor's token streams bit-match the
    padded mixed executor AND the legacy split executor over mixed
    prompt lengths with staggered arrivals — and its realized
    wasted-slot ratio is strictly below the padded path's."""
    cfg, fast_p, exp_p = tiny_parts
    rng = np.random.default_rng(0)
    lens = [1, 3, 5, 6, 10, 16]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    arrivals = [i % 3 for i in range(len(prompts))]
    delta = _pick_delta(cfg, fast_p, exp_p, prompts)
    rag, pad, _ = _three_way(cfg, fast_p, exp_p, prompts, arrivals,
                             deltas=[delta])
    assert {r.tier for r in rag.requests} == {0, 1}     # gate splits
    s_rag = rag.metrics.summary()
    s_pad = pad.metrics.summary()
    assert s_rag["wasted_slot_ratio"] < s_pad["wasted_slot_ratio"]
    # same launch discipline: one program per active tier per tick
    assert max(s_rag["launches_per_tick"]) <= 1.0 + 1e-9


def test_ragged_matches_split_oversubscribed_and_preemption(tiny_parts):
    """Stalls (block exhaustion) and evict-and-replay reorder work under
    the flat planner exactly as under the padded one: streams stay
    bit-identical across all three executors."""
    cfg, fast_p, exp_p = tiny_parts
    rng = np.random.default_rng(7)
    lens = [2, 16, 7, 11, 16, 4, 9, 1]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    kw = dict(slots=4, prefill_chunk=4, kv_blocks=[12, None])
    _three_way(cfg, fast_p, exp_p, prompts, **kw)
    kw["preemption_policy"] = "youngest"
    _three_way(cfg, fast_p, exp_p, prompts, **kw)


def test_ragged_matches_padded_with_prefix_cache(tiny_parts):
    """Shared-prefix admissions start rows mid-prompt (q_start > 0 at
    the first uncached chunk): the flat scatter and per-row position
    map must reproduce the padded streams exactly."""
    cfg, fast_p, exp_p = tiny_parts
    rng = np.random.default_rng(11)
    base = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    prompts = []
    for i in range(6):
        n = int(rng.integers(9, 17))
        p = base[:n].copy()
        p[8:] = rng.integers(0, cfg.vocab_size, n - 8)  # unique tails
        prompts.append(p)
    kw = dict(prefill_chunk=4, prefix_cache=True)
    rag, pad, _ = _three_way(cfg, fast_p, exp_p, prompts, **kw)
    assert sum(rag.metrics.prefix_hits_by_tier) > 0    # cache exercised


def test_ragged_gen_len_one(tiny_parts):
    cfg, fast_p, exp_p = tiny_parts
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (3, 8, 16)]
    rag, _, _ = _three_way(cfg, fast_p, exp_p, prompts, gen_len=1)
    assert all(len(r.tokens) == 1 for r in rag.requests)


# ---------------------------------------------------------------------------
# bucketed flat widths: plan packing + zero mid-run recompiles
# ---------------------------------------------------------------------------


def test_step_plan_flat_packing(tiny_parts):
    """The plan's flat fields: live tokens concatenated in slot order at
    the smallest covering bucket, per-token positions, and per-row
    q_start = each row's first absolute position this tick."""
    cfg, fast_p, _ = tiny_parts
    eng = CascadeEngine([TierSpec("t", cfg, fast_p)], slots=4,
                        prompt_len=32, gen_len=4, prefill_chunk=8,
                        deltas=[], clock=VirtualClock())
    eng.warmup()
    eng.submit(np.arange(6, dtype=np.int32) % 5)        # finishes tick 1
    eng.step()
    eng.submit(np.arange(20, dtype=np.int32) % 7)       # 3 chunks
    eng.step()                              # admit long; short decodes
    rt = eng.runtimes[0]
    plan = eng._build_plan(rt)
    [dec] = plan.decode_rows
    [pre] = plan.prefill_rows
    live = int(plan.q_len.sum())
    assert live == rt.chunk + 1
    assert plan.flat_width == rt.bucket_width(live) >= live
    assert plan.flat_width in rt.flat_buckets
    # slot-order packing: row order by slot id, each row contiguous
    flat_tok, flat_pos, o = plan.flat_tokens[0], plan.flat_pos[0], 0
    for s in sorted((dec, pre)):
        n = int(plan.q_len[s])
        np.testing.assert_array_equal(flat_tok[o:o + n],
                                      plan.tokens[s, :n])
        np.testing.assert_array_equal(
            flat_pos[o:o + n], plan.q_start[s] + np.arange(n))
        o += n
    assert (flat_tok[o:] == 0).all()
    assert plan.q_start[dec] == rt.pos[dec]
    assert plan.q_start[pre] == rt.prefill_pos[pre]


def test_no_mid_run_recompiles_across_mixed_run(tiny_parts):
    """Warmup compiles every bucket width; a mixed-length run launches
    only warmed widths — the compile counter shows zero mid-run
    recompiles (the legacy warmup's chunk + width-1 double-compile is
    gone: padded tiers warm exactly their two widths, ragged tiers
    their buckets)."""
    cfg, fast_p, exp_p = tiny_parts
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (1, 4, 16, 9, 2, 13)]
    eng = _drain(_mk(cfg, fast_p, exp_p), prompts,
                 arrivals=[i % 4 for i in range(6)])
    for st in eng.compile_stats():
        assert st["backend"] == "ragged"
        assert st["mid_run_recompiles"] == [], st
        assert set(st["launched_widths"]) <= set(st["warmed_widths"])
    # the run really exercised more than one bucket width
    assert any(len(st["launched_widths"]) > 1
               for st in eng.compile_stats())


def test_flat_bucket_validation(tiny_parts):
    cfg, fast_p, _ = tiny_parts
    kw = dict(slots=2, prompt_len=16, gen_len=2, deltas=[],
              prefill_chunk=8)
    # largest bucket must cover slots * chunk
    with pytest.raises(ValueError, match="cover the"):
        CascadeEngine([TierSpec("t", cfg, fast_p)], flat_buckets=[8],
                      **kw)
    # widths > 16 must be tile multiples
    with pytest.raises(ValueError, match="16-token query tile"):
        CascadeEngine([TierSpec("t", cfg, fast_p)],
                      flat_buckets=[8, 24], **kw)
    # ragged requires unified execution
    with pytest.raises(ValueError, match="ragged flat"):
        CascadeEngine([TierSpec("t", cfg, fast_p)],
                      use_unified_step=False, use_ragged_step=True, **kw)
    # custom buckets are honored
    eng = CascadeEngine([TierSpec("t", cfg, fast_p)],
                        flat_buckets=[4, 16, 32], **kw)
    assert eng.runtimes[0].flat_buckets == [4, 16, 32]
    assert eng.runtimes[0].bucket_width(5) == 16


# ---------------------------------------------------------------------------
# multi-device parity (subprocess, 8 simulated host devices)
# ---------------------------------------------------------------------------


def _run(code: str, timeout=540):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sharded_ragged_parity_vs_split():
    """Acceptance: on 8 simulated devices with per-tier data meshes, the
    ragged flat engine's token streams and escalation decisions
    bit-match the single-device split engine for uniform and lognormal
    lengths — the replicated flat batch mixes correctly with the
    row-sharded page tables and KV arena."""
    out = _run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving import CascadeEngine, TierSpec
    from repro.serving.engine import VirtualClock
    from repro.launch.mesh import make_tier_meshes

    assert jax.device_count() == 8, jax.device_count()
    fast = get_config("gemma3-1b", "smoke")
    exp = get_config("phi4-mini-3.8b", "smoke")
    fp = init_params(fast, jax.random.PRNGKey(0), jnp.float32)
    ep = init_params(exp, jax.random.PRNGKey(1), jnp.float32)
    vocab = min(fast.vocab_size, exp.vocab_size)

    def build(meshes, **kw):
        m = [None, None] if meshes is None else meshes
        eng = CascadeEngine(
            [TierSpec("fast", fast, fp, mesh=m[0]),
             TierSpec("exp", exp, ep, mesh=m[1])],
            deltas=[0.5], clock=VirtualClock(), **kw)
        eng.warmup()
        return eng

    def drain(eng, prompts):
        for p in prompts:
            eng.submit(np.asarray(p, np.int32), arrival_time=0.0)
        eng.run(max_steps=3000)
        return [(r.rid, tuple(r.tokens), r.tier,
                 tuple(r.seq_conf_by_tier)) for r in eng.requests]

    def check(base, other):
        assert len(base) == len(other)
        for a, b in zip(base, other):
            assert a[1] == b[1], (a, b)         # bit-identical tokens
            assert a[2] == b[2], (a, b)         # same escalation decisions
            assert np.allclose(a[3], b[3], atol=1e-5)

    rng = np.random.default_rng(7)
    PLEN, GLEN, N = 16, 4, 8
    uniform = [rng.integers(0, vocab, PLEN) for _ in range(N)]
    lens = np.clip(np.rint(rng.lognormal(np.log(PLEN / 4), 0.8, N)),
                   1, PLEN).astype(int)
    mixed = [rng.integers(0, vocab, L) for L in lens]
    kw = dict(slots=8, prompt_len=PLEN, gen_len=GLEN, prefill_chunk=8)
    for prompts in (uniform, mixed):
        meshes = make_tier_meshes([(4, 1), (4, 1)])
        split_1dev = drain(build(None, use_unified_step=False, **kw),
                           prompts)
        rag_shard = drain(build(meshes, **kw), prompts)
        check(split_1dev, rag_shard)
    print("RAGGED-PARITY-OK")
    """)
    assert "RAGGED-PARITY-OK" in out
