"""CascadeEngine: request-level cascade inference.

One engine step (tick) per tier:

  1. **admit** — pop queued/escalated requests into free KV slots
     (continuous batching: admission happens while other slots are mid
     decode).  Under chunked prefill (the default on block-paged,
     attention-only tiers) prompts of *any* length up to
     ``max_prompt_len`` are accepted; admission is bounded by a prompt
     **token budget** per tick and by free KV blocks for the first chunk.
  2. **plan** — a :class:`StepPlan` is built on the host: every live row
     is assigned its tick's work — the next fixed-size chunk of its
     prompt (``q_len = chunk`` or the shorter final tail), its single
     decode token (``q_len = 1``), or a stall (``q_len = 0``, block
     exhaustion) — into one flat ``[capacity, width]`` token batch.
  3. **execute** — **unified token-batch execution** (the default on
     block-paged attention-only tiers): the whole plan runs as ONE
     compiled mixed-attention program per tier per tick
     (``transformer.mixed_step`` over
     :mod:`repro.kernels.mixed_attention`), scattering prefill-chunk KV
     and decode-token KV through the page tables in the same program
     and emitting each row's last-position token + confidence through a
     single blocking ``device_get`` (``CascadeEngine.host_syncs``;
     test-asserted).  Per-token confidence comes from the Pallas
     :func:`repro.kernels.ops.confidence_gate` (max-softmax-prob, the
     paper's conf) or a jnp fallback.  A row's first token (argmax at
     its final prompt position) is emitted when its last chunk
     completes; it starts decoding next tick.  The **split** backend
     (``use_unified_step=False``, and always for dense-arena or
     recurrent-state tiers) executes the same plan as the legacy
     chunk_fn + step_fn pair — two launches on mixed ticks, first
     tokens flowing into the same-tick decode via a device-side
     ``where`` — with token streams bit-identical to unified.  The
     fully legacy path (``use_chunked_prefill=False``) packs
     uniform-length prompts densely, prefills in one shot, and scatters
     the caches — kept as the bit-exactness oracle and for
     recurrent-state models.
  4. **gate** — requests that hit ``gen_len`` aggregate their token
     confidences; at non-final tiers the scheduler's gate (fixed δ or
     escalation budget) decides DONE vs ESCALATED.  Escalated requests
     join the next tier's queue and are re-decoded there from scratch.

Admission and the tick's compute share **one token currency** under
unified execution: the per-tick token budget is pre-charged with the
carried load (decode tokens + in-flight prefill chunks) and a new
request bills only its first chunk — see :meth:`CascadeEngine._admit`.

**Sharded serving**: a tier whose :class:`TierSpec` carries a mesh runs
params, KV arena, and per-tick batches sharded across it — request rows
and the KV block pool partition over the mesh's data shards (shard-aware
admission binds a request's row and blocks on one shard), params
replicate or tensor-shard over 'model', and escalated requests are
re-packed on the host and ``device_put`` under the *target* tier's
sharding.  Token streams are bit-identical to the single-device engine
(multi-device parity suite: ``tests/test_sharded_serving.py``).

**Overload and failure** (docs/serving.md "Overload and failure
semantics"): when the KV block pool runs dry a ``preemption_policy``
(``youngest`` / ``fewest-tokens``) evicts a victim row instead of
stalling it — the victim re-queues as ``PREEMPTED`` and replays
prefill+decode from scratch through the idempotent chunk machinery
(greedy decode is deterministic, so the replayed stream is
bit-identical).  ``submit(deadline=)`` plus a per-tick shedding pass
reject queued requests that cannot meet their deadline (``SHED``).
Every launch and ``device_get`` runs under bounded retry-with-backoff
on transient errors; when retries exhaust the engine fails a single
victim request (``FAILED``), never the run.  Any other device error (an
OOM, a kernel fault) ends the run.  A
:class:`repro.serving.faults.FaultPlan` injects all of these conditions
deterministically behind zero-cost-when-None hooks.

The clock is injectable: ``WallClock`` for real Poisson traffic,
``VirtualClock`` for deterministic tests (one tick per step).
"""
from __future__ import annotations

import contextlib
import math
import time
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import ModelConfig
from repro.core import confidence as conf_lib
from repro.kernels import ops as kernel_ops
from repro.models import cache as cache_lib
from repro.models import params as params_lib
from repro.models import sharding as sharding_lib
from repro.models import transformer
from repro.serving import faults as faults_lib
from repro.serving import observability as obs
from repro.serving.metrics import ServingMetrics, TierCost
from repro.serving.request import Request, RequestState
from repro.serving.scheduler import CascadeScheduler, GateSpec
from repro.serving.slots import DenseTierSlotPool, TierSlotPool


@dataclass
class TierSpec:
    """One cascade member: model + params, and optionally its own mesh.

    ``mesh`` places the tier on a device mesh with ('data', 'model')
    axes (see ``launch/mesh.py::make_tier_mesh``): params are replicated
    across it (or tensor-sharded when ``shard_params`` — MaxText-style
    ``models/params.py::param_specs`` rules), the KV arena shards its
    request rows and block pool over the data axes, and every per-tick
    host input is ``device_put`` with the tier's row sharding.  Tiers
    may sit on disjoint device subsets (the usual production layout —
    the heavy tier gets more chips) or share devices.  ``mesh=None``
    keeps the single-device behaviour, bit-identical to a sharded run.
    """
    name: str
    cfg: ModelConfig
    params: object
    mesh: Optional[jax.sharding.Mesh] = None
    shard_params: bool = False

    def flops_per_request(self, gen_len: int) -> float:
        """Eq 7 cost: FLOPs/token = 2 * active params (as in launch.serve)."""
        return 2.0 * self.cfg.active_param_count() * gen_len

    def data_shards(self) -> int:
        return sharding_lib.data_axis_size(self.mesh)


class WallClock:
    def __init__(self):
        self._t0 = time.perf_counter()

    def reset(self) -> None:
        self._t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self._t0

    def wait_until(self, t: float) -> None:
        time.sleep(min(max(t - self.now(), 0.0), 0.05))

    def step_done(self) -> None:
        pass


class VirtualClock:
    """Deterministic clock: one tick per engine step."""

    def __init__(self, dt: float = 1.0):
        self.t = 0.0
        self.dt = dt

    def reset(self) -> None:
        self.t = 0.0

    def now(self) -> float:
        return self.t

    def wait_until(self, t: float) -> None:
        self.t = max(self.t, t)

    def step_done(self) -> None:
        self.t += self.dt


# per-row kinds in a StepPlan (KIND_DRAFT: a retained draft row catching
# up on its target request's emitted tokens and drafting ahead)
KIND_IDLE, KIND_PREFILL, KIND_DECODE, KIND_STALL, KIND_DRAFT = 0, 1, 2, 3, 4


@dataclass
class StepPlan:
    """One tier's tick, planned on the host before anything launches.

    Pure host-side data: per-row kind (idle / prefill chunk / decode
    token / stalled on block exhaustion), the packed token batch, per-slot
    absolute positions, live-query counts, and the data shard owning each
    row.  Built by :meth:`CascadeEngine._build_plan` from scheduler and
    slot-pool state, then executed by one of two backends behind the same
    interface:

    * **ragged flat** (the default on paged attention-only tiers): one
      :meth:`_TierRuntime.call_flat` launch consumes the flat packing —
      every live row's tokens concatenated into ``flat_tokens [1, W]``
      (W a bucketed power-of-two width), so the tick's compute is
      O(live tokens) end-to-end.
    * **padded unified** (``use_ragged_step=False``): one
      :meth:`_TierRuntime.run_mixed` launch consumes
      ``tokens``/``pos``/``q_len`` verbatim — every live row's work in a
      single compiled program per tick at ``[capacity, width]``.
    * **split** (``use_unified_step=False`` escape hatch; the only option
      for dense-arena and recurrent-state tiers): the legacy
      ``chunk_fn`` + ``step_fn`` pair, two launches on mixed ticks.

    The executors consume ``tokens``/``pos``/``q_len`` (or the flat
    fields) and the three row lists; ``kind`` and ``shard`` are the
    plan's per-row record of the same decisions (introspection: tests
    and debugging read them, the launch does not — a stall is equally
    expressed by exclusion from ``prefill_rows``/``decode_rows``).
    """
    width: int                  # token slots per row (chunk; 1 decode-only)
    kind: np.ndarray            # [capacity] int8 KIND_*
    tokens: np.ndarray          # [capacity, width] int32
    pos: np.ndarray             # [capacity, width] int32 abs positions
    q_len: np.ndarray           # [capacity] int32 live tokens per row
    shard: np.ndarray           # [capacity] int32 data shard of each row
    prefill_rows: List[int]     # live prefill rows (q_len > 0)
    decode_rows: List[int]      # decode rows (unified: stalls excluded)
    finishing: List[int]        # prefill rows whose last chunk completes
    # ragged flat layout (None on padded/split plans): live tokens of all
    # rows packed contiguously in slot order, padded up to the bucket
    flat_width: Optional[int] = None        # bucketed W >= sum(q_len)
    flat_tokens: Optional[np.ndarray] = None    # [1, W] int32
    flat_pos: Optional[np.ndarray] = None       # [1, W] int32 abs pos
    q_start: Optional[np.ndarray] = None        # [capacity] int32 row pos0
    # speculative cascade decoding (speculation_k > 0; empty otherwise):
    # verify rows are decode rows scoring drafted tokens (q_len = 1 + n),
    # draft rows are retained lower-tier rows catching up on their target
    # request's emitted tokens; draft_len[s] > 0 marks rows that draft
    # ahead after catching up (the device scan masks rows past their
    # per-row budget to the null block)
    verify_rows: List[tuple] = field(default_factory=list)  # (slot, n)
    draft_rows: List[int] = field(default_factory=list)
    draft_len: Optional[np.ndarray] = None      # [capacity] int32

    @property
    def live_prefill_tokens(self) -> int:
        return int(self.q_len[self.prefill_rows].sum()) \
            if self.prefill_rows else 0

    @property
    def live_tokens(self) -> int:
        """Real tokens this tick computes (prefill chunks + decode)."""
        return int(self.q_len.sum())

    def work(self) -> dict:
        """A ragged launch's work, as the tracer's ``launch`` span carries
        it: live ``tokens``; ``kv_read``, the keys the live rows read once
        per attention layer (Σ q_start + q_len); ``kv_pairs``, the
        query-key pairs attended (each live token's causal context);
        ``emitted``, the rows whose logits are used (finishing, decode and
        verify rows)."""
        q = self.q_len.astype(np.int64)
        start = self.q_start.astype(np.int64)
        return {"tokens": int(q.sum()),
                "kv_read": int((start + q)[q > 0].sum()),
                "kv_pairs": int((q * start + q * (q + 1) // 2).sum()),
                "emitted": len(self.finishing) + len(self.decode_rows)}


class _TierRuntime:
    """Per-tier compiled functions + host-side slot state.

    With a tier mesh the runtime owns the device placement seam: params
    are ``device_put`` once at construction (replicated or
    tensor-sharded), every per-tick host array goes through
    :meth:`put_rows` (row dim sharded over the data axes — this is also
    the escalation transfer path: an escalated request's prompt chunks
    are packed on the host and placed under the *target* tier's
    sharding), and the jitted functions run inside the tier's mesh
    context so ``shard_hint`` constraints resolve against it.
    """

    def __init__(self, spec: TierSpec, capacity: int, prompt_len: int,
                 max_seq: int, use_gate_kernel: bool, *,
                 use_paged_kv: bool = True, block_size: int = 16,
                 kv_blocks: Optional[int] = None,
                 use_chunked_prefill: bool = False,
                 prefill_chunk: int = 128,
                 use_unified_step: bool = False,
                 use_ragged_step: bool = False,
                 flat_buckets: Optional[Sequence[int]] = None,
                 prefix_cache: bool = False,
                 speculation_k: int = 0,
                 spec_draft: bool = False):
        self.spec = spec
        self.capacity = capacity
        self.prompt_len = prompt_len          # max prompt length (tokens)
        self.paged = use_paged_kv
        self.chunked = use_chunked_prefill
        self.unified = use_unified_step and use_chunked_prefill
        self.ragged = bool(use_ragged_step) and self.unified
        self.chunk = min(prefill_chunk, prompt_len)
        # ragged flat widths: compiled program shapes are drawn from a
        # small fixed bucket set (powers of two up to the worst-case
        # capacity*chunk tick), so a mixed-length run never recompiles
        # mid-run; warmed/launched width sets feed the compile counter
        self.flat_buckets = (self._default_buckets()
                             if flat_buckets is None
                             else self._validate_buckets(flat_buckets))
        self.warmed_widths: set = set()
        self.launched_widths: set = set()
        self.prefix = bool(prefix_cache) and self.paged and self.chunked
        self.mesh = spec.mesh
        self.data_shards = spec.data_shards()
        if capacity % self.data_shards:
            raise ValueError(
                f"tier {spec.name}: {capacity} slots must divide into the "
                f"mesh's {self.data_shards} data shards")
        # the KV arena is stored in the tier's param dtype: float32 for
        # the smoke tiers, bfloat16 at published widths
        kv_dtype = spec.params["embed"].dtype
        if use_paged_kv:
            self.pool = TierSlotPool(spec.cfg, capacity, max_seq, kv_dtype,
                                     block_size=block_size,
                                     num_blocks=kv_blocks, mesh=spec.mesh,
                                     prefix_chunk=(self.chunk if self.prefix
                                                   else None))
        else:
            self.pool = DenseTierSlotPool(spec.cfg, capacity, max_seq,
                                          kv_dtype, mesh=spec.mesh)
        self.params = self._place_params(spec)
        self.slot_req: List[Optional[Request]] = [None] * capacity
        self.tok = np.zeros(capacity, np.int32)
        self.pos = np.zeros(capacity, np.int32)
        self.prefill_pos = np.zeros(capacity, np.int32)   # tokens written
        # speculative cascade decoding: spec_k > 0 swaps the tier's
        # ragged launch for spec_fn (ragged forward + fused accept/reject
        # epilogue + optional draft scan — still ONE program per tick);
        # draft_req maps retained draft rows to their escalated target
        # request (slot_req stays None there, so every slot_req-driven
        # path — planning, victim picking, finish — skips them for free)
        self.spec_k = int(speculation_k)
        self.spec_draft = bool(spec_draft) and self.spec_k > 0
        self.draft_req: List[Optional[Request]] = [None] * capacity
        cfg = spec.cfg

        def pick(logits2d):
            if use_gate_kernel:
                gate = kernel_ops.confidence_gate(logits2d)
                return gate["argmax"].astype(jnp.int32), gate["conf"]
            return (jnp.argmax(logits2d, -1).astype(jnp.int32),
                    conf_lib.max_prob(logits2d))

        def prefill_fn(params, prompts):
            batch = {"tokens": prompts}
            if cfg.frontend:
                batch["frontend_embeds"] = jnp.zeros(
                    (prompts.shape[0], cfg.frontend_len, cfg.frontend_dim),
                    jnp.float32)
            logits, part_cache, _ = transformer.forward(
                params, cfg, batch, mode="prefill")
            tok, conf = pick(logits[:, -1])
            return part_cache, tok, conf

        def step_fn(params, tok, cache, pos, page_table):
            pages = {"page_table": page_table} if use_paged_kv else None
            logits, new_cache = transformer.decode_step(
                params, cfg, tok, cache, pos, pages=pages)
            nxt, conf = pick(logits[:, 0])
            return nxt, conf, new_cache

        def chunk_fn(params, tokens, cache, pos, page_table, q_len):
            logits, new_cache = transformer.prefill_chunk(
                params, cfg, tokens, cache, pos,
                {"page_table": page_table, "q_len": q_len})
            # first generated token = argmax at each row's last live
            # prompt position; host keeps it only for final chunks
            rows = jnp.arange(logits.shape[0])
            last = jnp.maximum(q_len - 1, 0)
            tok, conf = pick(logits[rows, last])
            return tok, conf, new_cache

        def mixed_fn(params, tokens, cache, pos, page_table, q_len):
            # unified token-batch step: every live row's work — prefill
            # chunk or decode token — in ONE compiled program; q_len
            # selects each row's last live position for the gate
            pages = {"page_table": page_table, "q_len": q_len}
            logits, new_cache = transformer.mixed_step(
                params, cfg, tokens, cache, pos, pages)
            tok, conf = pick(logits)
            return tok, conf, new_cache

        def ragged_fn(params, tokens, cache, pos, page_table, q_len,
                      q_start):
            # ragged flat token-batch step: the tick's live tokens packed
            # contiguously in [1, W] (W bucketed), so compute is O(live
            # tokens) instead of O(capacity * width); returns per-row
            # last-position picks in engine-row order like mixed_fn
            pages = {"page_table": page_table, "q_len": q_len,
                     "q_start": q_start}
            logits, new_cache = transformer.ragged_step(
                params, cfg, tokens, cache, pos, pages)
            tok, conf = pick(logits)
            return tok, conf, new_cache

        k = self.spec_k
        do_draft = self.spec_draft

        def spec_fn(params, tokens, cache, pos, page_table, q_len,
                    q_start, draft_len):
            # speculative ragged step: the ragged forward keeps *all*
            # per-position logits so verify rows (q_len = 1 + n) score
            # every drafted position, the fused spec_accept epilogue
            # decides acceptance device-side, and (draft tiers only) a
            # k-1 step decode scan extends each drafting row's catch-up
            # pick into k draft tokens — one compiled program, one fetch
            pages = {"page_table": page_table, "q_len": q_len,
                     "q_start": q_start}
            logits, new_cache = transformer.ragged_verify(
                params, cfg, tokens, cache, pos, pages)
            am, cf = pick(logits[0])
            out = kernel_ops.spec_accept(am, cf, q_len, tokens, k)
            tok, conf = out["tok"], out["conf"]
            draft_tok = jnp.zeros((q_len.shape[0], k), jnp.int32)
            draft_conf = jnp.zeros((q_len.shape[0], k), jnp.float32)
            if do_draft:
                def body(carry, j):
                    cache_c, cur_tok, cur_pos = carry
                    # rows whose per-row draft budget is spent (or that
                    # aren't drafting) mask to the null block: their
                    # writes and outputs are discarded
                    live = draft_len > j
                    pt = jnp.where(live[:, None], page_table, 0)
                    dl, cache_c = transformer.decode_step(
                        params, cfg, cur_tok[:, None], cache_c,
                        jnp.where(live, cur_pos, 0)[:, None],
                        pages={"page_table": pt})
                    t2, c2 = pick(dl[:, 0])
                    return (cache_c, t2, cur_pos + 1), (t2, c2)

                if k > 1:
                    # q_start is each row's starting *sequence* position,
                    # so q_start + q_len is where its first scan step
                    # writes (one past the catch-up chunk)
                    (new_cache, _, _), (dt, dc) = jax.lax.scan(
                        body, (new_cache, tok, q_start + q_len),
                        jnp.arange(1, k))
                    draft_tok = jnp.concatenate([tok[None], dt]).T
                    draft_conf = jnp.concatenate([conf[None], dc]).T
                else:
                    draft_tok = tok[:, None]
                    draft_conf = conf[:, None]
            return (tok, conf, out["spec_tok"], out["spec_conf"],
                    out["acc_len"], draft_tok, draft_conf, new_cache)

        self.prefill_fn = jax.jit(prefill_fn)
        # Donate the cache so XLA updates the slot arena in place instead
        # of copying it every token (2x peak cache memory otherwise).
        donate = (2,)
        self.step_fn = jax.jit(step_fn, donate_argnums=donate)
        self.chunk_fn = jax.jit(chunk_fn, donate_argnums=donate)
        self.mixed_fn = jax.jit(mixed_fn, donate_argnums=donate)
        self.ragged_fn = jax.jit(ragged_fn, donate_argnums=donate)
        self.spec_fn = (jax.jit(spec_fn, donate_argnums=donate)
                        if self.spec_k and self.ragged else None)

    # -- ragged flat-width buckets ------------------------------------------

    def _default_buckets(self) -> List[int]:
        """Powers of two from 8 up to the first covering the worst-case
        tick (every row prefilling a full chunk = capacity * chunk live
        tokens)."""
        worst = max(self.capacity * self.chunk, 1)
        buckets, w = [], 8
        while w < worst:
            buckets.append(w)
            w *= 2
        buckets.append(w)
        return buckets

    def _validate_buckets(self, buckets: Sequence[int]) -> List[int]:
        out = sorted({int(b) for b in buckets})
        if not out or out[0] <= 0:
            raise ValueError(f"flat_buckets must be positive: {buckets}")
        for b in out:
            if b > 16 and b % 16:
                raise ValueError(
                    f"flat bucket {b} must be a multiple of the ragged "
                    "kernel's 16-token query tile (widths <= 16 are "
                    "single-tile and exempt)")
        worst = self.capacity * self.chunk
        if out[-1] < worst:
            raise ValueError(
                f"largest flat bucket {out[-1]} cannot cover the "
                f"worst-case tick of {worst} live tokens "
                f"({self.capacity} slots x {self.chunk}-token chunks)")
        return out

    def bucket_width(self, live_tokens: int) -> int:
        """Smallest bucket holding `live_tokens` (>= 1 slot)."""
        need = max(int(live_tokens), 1)
        for b in self.flat_buckets:
            if b >= need:
                return b
        return self.flat_buckets[-1]

    # -- device placement ---------------------------------------------------

    def _place_params(self, spec: TierSpec):
        """Params on the tier mesh: replicated, or tensor-sharded over
        'model' per the MaxText-style logical-axis rules when
        ``spec.shard_params``."""
        if spec.mesh is None:
            return spec.params
        if spec.shard_params:
            shardings = jax.tree.map(
                lambda ps: NamedSharding(spec.mesh, ps),
                params_lib.param_specs(spec.cfg, spec.mesh),
                is_leaf=lambda x: isinstance(x, PartitionSpec))
        else:
            shardings = jax.tree.map(
                lambda _: NamedSharding(spec.mesh, PartitionSpec()),
                spec.params)
        return jax.device_put(spec.params, shardings)

    def put_rows(self, arr):
        """A per-tick host array onto the tier's devices, row dim sharded
        over the data axes (no mesh: plain transfer).  Used for tokens,
        positions, chunk batches, and page tables — and thereby the
        escalation transfer path: a request escalated from another tier
        is packed into this tier's fixed-shape batches on the host and
        placed under *this* tier's sharding here."""
        arr = np.asarray(arr)
        if self.mesh is None:
            return jnp.asarray(arr)
        spec = PartitionSpec(*(("data",) + (None,) * (arr.ndim - 1)))
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def _ctx(self):
        """The tier's mesh context (shard_hint constraints resolve
        against it); a no-op without a mesh."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def run_prefill(self, prompts):
        with self._ctx():
            return self.prefill_fn(self.params, self.put_rows(prompts))

    def run_chunk(self, tokens, pos, qlen):
        with self._ctx():
            return self.chunk_fn(
                self.params, self.put_rows(tokens), self.pool.cache,
                self.put_rows(pos), self.page_table_device(),
                self.put_rows(qlen))

    def run_step(self, tok_dev, mask_rows):
        with self._ctx():
            return self.step_fn(
                self.params, tok_dev, self.pool.cache,
                self.put_rows(self.pos[:, None]),
                self.page_table_device(mask_rows=mask_rows))

    def run_mixed(self, tokens, pos, qlen):
        """The padded unified token-batch launch: one compiled program
        serves every live row's tick — prefill chunks and decode tokens
        share the batch, so no page-table masking is needed (each row
        scatters into and attends its *own* pages inside the same
        program)."""
        self.launched_widths.add(int(np.asarray(tokens).shape[1]))
        with self._ctx():
            return self.mixed_fn(
                self.params, self.put_rows(tokens), self.pool.cache,
                self.put_rows(pos), self.page_table_device(),
                self.put_rows(qlen))

    def put_flat(self, arr):
        """A flat ``[1, W]`` per-tick array onto the tier's devices,
        replicated (the leading dim is not the row dim, so it cannot
        shard over the data axes; GSPMD mixes the replicated flat batch
        with the row-sharded page table and KV arena)."""
        arr = np.asarray(arr)
        if self.mesh is None:
            return jnp.asarray(arr)
        return jax.device_put(arr, NamedSharding(self.mesh,
                                                 PartitionSpec()))

    def stage_flat(self, flat_tokens, flat_pos, qlen, qstart,
                   draft_len=None):
        """The host->device puts of one ragged launch, in the step's
        argument order: flat tokens, flat positions, page table,
        ``q_len``, ``q_start``, and with ``draft_len [capacity]`` the
        speculative step's per-row draft budget."""
        self.launched_widths.add(int(np.asarray(flat_tokens).shape[1]))
        staged = (self.put_flat(flat_tokens), self.put_flat(flat_pos),
                  self.page_table_device(), self.put_rows(qlen),
                  self.put_rows(qstart))
        if draft_len is not None:
            staged += (self.put_rows(draft_len),)
        return staged

    def call_flat(self, spec: bool, staged):
        """The ragged flat token-batch launch on :meth:`stage_flat`'s
        arguments: ONE compiled program at a bucketed flat width serves
        the tick's live tokens — each token scatters KV through and
        attends its owning row's pages, so the program's compute is
        O(live tokens), not O(capacity * width).  With ``spec`` it is the
        speculative step (``speculation_k > 0``, ``staged`` holding the
        draft budget), whose fused draft scan runs in the same one
        program per tier per tick."""
        fn = self.spec_fn if spec else self.ragged_fn
        tokens, pos, *rest = staged
        with self._ctx():
            return fn(self.params, tokens, self.pool.cache, pos, *rest)

    def page_table_device(self, mask_rows: Sequence[int] = ()):
        """Device page tables; ``mask_rows`` (rows mid-prefill during a
        decode step) have their pages unmapped in the copy so the decode
        scatter/gather for those rows hits the null block instead of the
        blocks their prefill chunks are filling."""
        if self.paged:
            pt = self.pool.page_table
            if len(mask_rows):
                pt = pt.copy()
                pt[list(mask_rows)] = 0
            return self.put_rows(pt)
        # dense pools take a dummy (the traced fn ignores it)
        return self.put_rows(np.zeros((self.capacity, 1), np.int32))

    def occupied(self) -> List[int]:
        return [s for s, r in enumerate(self.slot_req) if r is not None]

    def decoding(self) -> List[int]:
        return [s for s, r in enumerate(self.slot_req)
                if r is not None and r.state is RequestState.DECODE
                and not r.decode_finished]

    def prefilling(self) -> List[int]:
        return [s for s, r in enumerate(self.slot_req)
                if r is not None and r.state is RequestState.PREFILL]

    def draft_slots(self) -> List[int]:
        """Rows retained as draft rows for escalated requests."""
        return [s for s, r in enumerate(self.draft_req) if r is not None]


class _RetryExhausted(RuntimeError):
    """Internal: a launch's bounded retry budget ran out on persistent
    transient errors.  The engine catches this at each launch site and
    sacrifices a single victim request — never the run."""

    def __init__(self, kind: str, cause: BaseException):
        super().__init__(f"launch retries exhausted in {kind}: {cause}")
        self.kind = kind
        self.cause = cause


# runtime statuses a relaunch can cure (a dropped transfer, a collective
# timeout); any other status — RESOURCE_EXHAUSTED (device OOM), INTERNAL
# (a kernel fault), INVALID_ARGUMENT — fails the same way again
_TRANSIENT_STATUSES = ("UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED")


def _is_transient(e: BaseException) -> bool:
    """Whether the retry wrapper may relaunch after ``e``: an injected
    :class:`repro.serving.faults.TransientError`, or a jax runtime error
    whose status is in :data:`_TRANSIENT_STATUSES`."""
    if isinstance(e, faults_lib.TransientError):
        return True
    return (isinstance(e, jax.errors.JaxRuntimeError)
            and str(e).startswith(_TRANSIENT_STATUSES))


class CascadeEngine:
    """M-tier cascade with continuous batching and per-request gating."""

    def __init__(self, tiers: Sequence[TierSpec], *,
                 slots: int | Sequence[int] = 8,
                 prompt_len: int = 32, gen_len: int = 16,
                 deltas: Optional[Sequence[float]] = None,
                 escalation_budget: Optional[float] = None,
                 conf_reduce: str = "mean",
                 use_gate_kernel: bool = True,
                 use_paged_kv: bool = True,
                 kv_block_size: int = 16,
                 kv_blocks: Optional[int | Sequence[Optional[int]]] = None,
                 use_chunked_prefill: Optional[bool] = None,
                 prefill_chunk: int = 128,
                 prefill_token_budget: Optional[int] = None,
                 use_unified_step: Optional[bool] = None,
                 use_ragged_step: Optional[bool] = None,
                 flat_buckets: Optional[Sequence[int]] = None,
                 prefix_cache: bool = False,
                 speculation_k: int = 0,
                 spec_delta: Optional[float] = None,
                 tracer: Optional[obs.Tracer] = None,
                 profile_annotations: bool = False,
                 clock=None,
                 preemption_policy: str = "none",
                 launch_retries: int = 2,
                 retry_backoff: float = 0.02,
                 faults: Optional[faults_lib.FaultPlan] = None):
        """``use_paged_kv`` selects the block-paged KV arena + Pallas
        paged flash-decode kernel (interpret mode off-TPU); False keeps
        the PR 1 dense one-page-per-request arena (the reference path).
        ``kv_blocks`` sizes each tier's arena in KV *blocks* of
        ``kv_block_size`` tokens — None fully provisions
        (``slots * ceil(max_seq / block_size) + 1``); a smaller count
        over-subscribes the arena: admission is then block-limited and
        rows may stall a tick waiting for a free block (attention-only
        models; recurrent state cannot replay a stalled step).

        ``use_chunked_prefill`` (default: auto — on whenever the arena is
        paged and every tier is attention-only with no modality frontend)
        replaces the dense packed prefill with **chunked paged prefill**:
        ``prompt_len`` becomes the *maximum* prompt length, ``submit``
        accepts any length in ``[1, prompt_len]``, and each admitted row
        advances ``prefill_chunk`` prompt tokens per tick written directly
        into its KV blocks.  Admission is bounded by
        ``prefill_token_budget`` prompt tokens per tier per tick (default
        ``slots * prefill_chunk``).  ``use_chunked_prefill=False`` keeps
        the uniform-length packed prefill (exact ``prompt_len`` enforced
        at submit) — the bit-exactness oracle for the chunked path.

        ``use_unified_step`` (default: auto — on exactly when chunked
        prefill is on) selects **unified token-batch execution**: each
        tick builds one flat token batch in which every live row
        contributes its next prefill chunk or its single decode token,
        executed by ONE compiled mixed-attention program per tier per
        tick (``transformer.mixed_step`` over
        ``kernels/mixed_attention.py``) with one blocking ``device_get``.
        The per-tick token budget then spans prefill chunks *and* decode
        tokens uniformly: admission charges a request's first chunk
        against the same currency the tick's carried decode+chunk load
        already occupies.  ``use_unified_step=False`` is the split-path
        escape hatch (legacy ``chunk_fn`` + ``step_fn``, two launches on
        mixed ticks) — the A/B baseline; token streams are bit-identical
        between the two.

        ``use_ragged_step`` (default: auto — on exactly when unified
        execution is on) selects the **ragged flat token-batch layout**
        inside unified execution: each tick's live tokens are packed
        contiguously into one ``[1, W]`` flat batch (W drawn from a
        small power-of-two bucket set, ``flat_buckets``), executed by
        ONE compiled ragged-attention program per tier per tick
        (``transformer.ragged_step`` over
        ``kernels/ragged_attention.py``) whose compute is O(live
        tokens) end-to-end — idle slots cost nothing instead of a
        padded row.  All bucket widths compile at :meth:`warmup`, so a
        mixed-length run never recompiles mid-run
        (:meth:`compile_stats`).  ``use_ragged_step=False`` keeps the
        padded ``[capacity, width]`` mixed program — the bit-identical
        escape hatch and A/B baseline; ``flat_buckets`` overrides the
        bucket set (each width > 16 must be a multiple of the kernel's
        16-token query tile, and the largest must cover
        ``capacity * prefill_chunk``).

        ``tracer`` attaches a :class:`repro.serving.observability.Tracer`:
        the engine then records per-request lifecycle spans and per-tick
        phase events (admit / plan / launch / device_get / finish) into
        its ring buffer for Chrome-trace export.  ``tracer=None``
        (default) is zero-cost — every trace call site is guarded, no
        event objects are built, and no extra host syncs happen either
        way (events only use values the tick already fetched;
        test-asserted).  ``profile_annotations`` additionally wraps each
        tick in ``jax.profiler.StepTraceAnnotation`` (step_num = tick
        id) and each launch in a named ``TraceAnnotation`` so an opt-in
        device-profiler window correlates with the host tracer.

        ``prefix_cache`` turns on **refcounted prefix caching** (requires
        the chunked block-paged path): each tier's pool keeps a
        per-shard prefix index over chunk-aligned prompt prefixes, and
        admission matches a submitted prompt's longest cached prefix,
        maps those KV blocks read-only into the new row's page table
        (copy-on-write isolates any block a boundary splits), and starts
        chunked prefill at the first uncached token — cached tokens cost
        0 prefill work and 0 admission budget.  Completed chunk
        boundaries are published back to the index as rows prefill;
        eviction is refcount-aware LRU (docs/serving.md "Prefix
        caching").  Token streams are bit-identical with the cache on or
        off under a fixed-δ gate: shared KV equals what re-prefilling
        the same tokens would write, and greedy decode is deterministic.

        ``preemption_policy`` trades stalls for evictions when the KV
        block pool runs dry (docs/serving.md "Overload and failure
        semantics"): ``youngest`` evicts the most recently bound row on
        a stalled shard, ``fewest-tokens`` the least-progressed one; the
        victim re-queues at the head of its tier's queue and replays
        prefill+decode from scratch (bit-identical — greedy decode is
        deterministic).  Requires the chunked block-paged path; a
        shard's oldest bound row is never evicted, so the oldest-first
        termination argument survives.  ``launch_retries`` bounds the
        retry-with-backoff wrapper around every launch and ``device_get``
        (``retry_backoff`` seconds, doubling); when retries exhaust the
        engine fails one victim request, never the run.  ``faults``
        attaches a :class:`repro.serving.faults.FaultPlan` — zero-cost
        when None, like the tracer."""
        if not tiers:
            raise ValueError("need at least one tier")
        self.tiers = list(tiers)
        m = len(self.tiers)
        chunkable = use_paged_kv and all(
            not cache_lib.has_recurrent_state(t.cfg) and t.cfg.frontend
            is None for t in self.tiers)
        if use_chunked_prefill is None:
            use_chunked_prefill = chunkable
        elif use_chunked_prefill and not chunkable:
            raise ValueError(
                "chunked prefill requires the block-paged KV arena "
                "(use_paged_kv=True) and attention-only tiers without a "
                "modality frontend (recurrent state cannot be carried "
                "across prefill chunks)")
        self.chunked_prefill = use_chunked_prefill
        if use_unified_step is None:
            use_unified_step = use_chunked_prefill
        elif use_unified_step and not use_chunked_prefill:
            raise ValueError(
                "unified token-batch execution requires chunked paged "
                "prefill (use_paged_kv=True, attention-only tiers); dense "
                "and recurrent-state tiers keep the legacy split "
                "chunk+decode path (use_unified_step=False)")
        self.unified_step = use_unified_step
        if use_ragged_step is None:
            use_ragged_step = use_unified_step
        elif use_ragged_step and not use_unified_step:
            raise ValueError(
                "the ragged flat token-batch layout runs inside unified "
                "token-batch execution (use_unified_step=True); the split "
                "and dense paths have no flat batch to pack")
        self.ragged_step = bool(use_ragged_step) and use_unified_step
        if flat_buckets is not None and not self.ragged_step:
            raise ValueError(
                "flat_buckets sizes the ragged flat layout's compiled "
                "widths; it requires use_ragged_step")
        if prefix_cache and not use_chunked_prefill:
            raise ValueError(
                "prefix caching requires chunked paged prefill "
                "(use_paged_kv=True, attention-only tiers): shared prefix "
                "blocks are matched and published at chunk boundaries, and "
                "the resumed prefill starts mid-prompt")
        self.prefix_cache = bool(prefix_cache)
        if speculation_k:
            if speculation_k < 0:
                raise ValueError("speculation_k must be >= 0")
            if m < 2:
                raise ValueError(
                    "speculative cascade decoding needs at least two "
                    "tiers: a cheap tier to draft and an expensive tier "
                    "to verify")
            if not self.ragged_step:
                raise ValueError(
                    "speculative cascade decoding requires the ragged "
                    "flat token-batch layout (use_ragged_step=True): the "
                    "verify pass scores k+1 positions per row through "
                    "the arbitrary-q_len work list")
        if spec_delta is not None and not speculation_k:
            raise ValueError(
                "spec_delta truncates staged drafts; it requires "
                "speculation_k > 0")
        self.speculation_k = int(speculation_k)
        self.spec_delta = None if spec_delta is None else float(spec_delta)
        if prefill_chunk <= 0:
            raise ValueError("prefill_chunk must be positive")
        slots_per_tier = ([int(slots)] * m if np.isscalar(slots)
                          else [int(s) for s in slots])
        kv_blocks_per_tier = (
            [kv_blocks] * m if kv_blocks is None or np.isscalar(kv_blocks)
            else [None if b is None else int(b) for b in kv_blocks])
        if len(slots_per_tier) != m or len(kv_blocks_per_tier) != m:
            raise ValueError(
                f"per-tier sequences must match the {m} tiers: got "
                f"{len(slots_per_tier)} slots, "
                f"{len(kv_blocks_per_tier)} kv_blocks entries")
        if deltas is not None:
            gates = [GateSpec(delta=float(d)) for d in deltas]
        elif escalation_budget is not None:
            gates = [GateSpec(budget=float(escalation_budget))
                     for _ in range(m - 1)]
        else:
            gates = [GateSpec(delta=0.5) for _ in range(m - 1)]
        if len(gates) != m - 1:
            raise ValueError("one gate per non-final tier")

        self.prompt_len = prompt_len        # chunked: max prompt length
        self.gen_len = gen_len
        self.conf_reduce = conf_reduce
        self.prefill_chunk = min(prefill_chunk, prompt_len)
        self.prefill_token_budget = (
            prefill_token_budget if prefill_token_budget is not None
            else max(slots_per_tier) * self.prefill_chunk)
        # sharded serving: each tier's rows partition over its mesh's
        # data shards; admission targets the shard whose block pool can
        # take the request (validated against slots in _TierRuntime)
        shards_per_tier = [t.data_shards() for t in self.tiers]
        self.metrics = ServingMetrics(
            [TierCost(t.name, t.flops_per_request(gen_len))
             for t in self.tiers], slots_per_tier)
        # the scheduler streams every gate decision into the metrics'
        # calibration telemetry; the engine streams escalation outcomes
        self.scheduler = CascadeScheduler(
            slots_per_tier, gates, shards_per_tier,
            calibration=self.metrics.calibration)
        self.clock = clock if clock is not None else WallClock()
        self.tracer = tracer
        self.profile_annotations = bool(profile_annotations)
        self.tick_id = 0
        if tracer is not None:
            tracer.name_process(obs.ENGINE_PID, "engine ticks")
            # tid layout on the engine pid: one lane per tier, plus a
            # whole-tick umbrella lane at tid = num_tiers
            tracer.name_track(obs.ENGINE_PID, len(self.tiers), "tick")
            for i, t in enumerate(self.tiers):
                tracer.name_track(obs.ENGINE_PID, i, f"tier{i} {t.name}")
                tracer.name_process(obs.REQUEST_PID_BASE + i,
                                    f"requests tier{i} {t.name}")
        max_seq = prompt_len + gen_len
        if use_paged_kv:
            ppr = math.ceil(max_seq / kv_block_size)
            for spec, cap, nb in zip(self.tiers, slots_per_tier,
                                     kv_blocks_per_tier):
                if nb is not None and nb < cap * ppr + 1 \
                        and cache_lib.has_recurrent_state(spec.cfg):
                    raise ValueError(
                        f"tier {spec.name}: kv_blocks={nb} over-subscribes "
                        "the arena but the model carries recurrent state "
                        "(mamba/rwkv), which cannot replay a stalled "
                        "decode step — use full provisioning (kv_blocks="
                        "None)")
        self.runtimes = [
            _TierRuntime(spec, cap, prompt_len, max_seq, use_gate_kernel,
                         use_paged_kv=use_paged_kv, block_size=kv_block_size,
                         kv_blocks=nb,
                         use_chunked_prefill=use_chunked_prefill,
                         prefill_chunk=self.prefill_chunk,
                         use_unified_step=use_unified_step,
                         use_ragged_step=self.ragged_step,
                         flat_buckets=flat_buckets,
                         prefix_cache=prefix_cache,
                         speculation_k=self.speculation_k,
                         spec_draft=(i < m - 1))
            for i, (spec, cap, nb) in enumerate(
                zip(self.tiers, slots_per_tier, kv_blocks_per_tier))]
        self.requests: List[Request] = []
        self._rid = 0
        # per-tier token-budget window state, reset each tick: tokens
        # charged (unified: seeded with the tick's carried decode+chunk
        # load — one currency) and requests admitted (never-starve guard)
        self._budget_used = [0] * m
        self._admitted = [0] * m
        self.host_syncs = 0                 # blocking device->host fetches
        # -- overload & failure layer (module docstring) -------------------
        if preemption_policy not in ("none", "youngest", "fewest-tokens"):
            raise ValueError(
                f"unknown preemption_policy {preemption_policy!r} "
                "(choose none / youngest / fewest-tokens)")
        if preemption_policy != "none" and not use_chunked_prefill:
            raise ValueError(
                "preemption requires the block-paged arena with chunked "
                "prefill: the replay path re-runs the victim's prefill "
                "through the idempotent chunk machinery")
        self.preemption_policy = preemption_policy
        if launch_retries < 0:
            raise ValueError("launch_retries must be >= 0")
        self.launch_retries = int(launch_retries)
        self.retry_backoff = float(retry_backoff)
        self.faults = faults
        self._has_deadlines = False         # any submit carried a deadline
        self._min_tick_dt: Optional[float] = None   # shedding floor unit
        self._last_tick_t: Optional[float] = None
        self._last_stalls = [0] * m         # per tier, for drain diagnostics

    # -- submission --------------------------------------------------------

    def submit(self, prompt, arrival_time: float = 0.0,
               deadline: Optional[float] = None) -> Request:
        """Queue one request.  ``deadline`` (absolute, in the engine's
        clock domain) opts it into load shedding: the per-tick shedding
        pass rejects it (terminal ``SHED``) once the deadline has passed
        or provably cannot be met (see :meth:`_service_floor`)."""
        prompt = np.asarray(prompt, np.int32)
        if self.chunked_prefill:
            if prompt.ndim != 1 or not 1 <= prompt.shape[0] <= self.prompt_len:
                raise ValueError(
                    f"prompt must be 1D with 1..{self.prompt_len} tokens, "
                    f"got shape {prompt.shape}")
        elif prompt.shape != (self.prompt_len,):
            raise ValueError(
                f"prompt must be [{self.prompt_len}], got {prompt.shape} "
                "(the uniform packed prefill batches one prompt length; "
                "use chunked prefill for mixed lengths)")
        req = Request(rid=self._rid, prompt=prompt, gen_len=self.gen_len,
                      arrival_time=float(arrival_time),
                      deadline=None if deadline is None else float(deadline))
        self._rid += 1
        self.requests.append(req)
        self.scheduler.submit(req)
        self.metrics.record_submitted()
        if deadline is not None:
            self._has_deadlines = True
        if self.tracer is not None:
            self.tracer.request_transition(
                req.rid, "QUEUED", 0, prompt_tokens=req.prompt_tokens)
        return req

    # -- one engine tick ---------------------------------------------------

    def _fetch(self, tier: int, tree):
        """The tick's blocking device->host transfer (counted overall and
        per tier: the sync-coalescing tests assert a mixed prefill+decode
        tick pays exactly one of these per active tier).  Traced as the
        ``device_get`` phase — its duration is where device compute the
        host must wait for shows up on the timeline.  Runs under the
        retry wrapper (side-effect-free: re-fetching re-reads the same
        device buffers); exhaustion here is engine-fatal — the tick's
        results are unrecoverable without the transfer."""
        self.host_syncs += 1
        self.metrics.record_host_sync(tier)
        tr = self.tracer
        if tr is None:
            return self._launch(tier, "device_get",
                                lambda: jax.device_get(tree))
        t0 = tr.now_us()
        out = self._launch(tier, "device_get", lambda: jax.device_get(tree))
        tr.phase("device_get", tier, t0, tick=self.tick_id)
        return out

    def _launch(self, tier: int, kind: str, thunk):
        """Run one launch/transfer under bounded retry-with-backoff.
        Transient failures (see :func:`_is_transient`) retry up to
        ``launch_retries`` times with doubling ``retry_backoff``;
        relaunching is safe because the tick's plan is pure host data
        built *before* any host state advances — replaying it rewrites
        the same KV pages idempotently.  Exhaustion raises
        :class:`_RetryExhausted` for the call site to sacrifice a single
        victim request (see :meth:`_fail_one`).  Any other error — a
        device OOM, a kernel fault — propagates and ends the run."""
        delay = self.retry_backoff
        attempt = 0
        while True:
            try:
                if self.faults is not None:
                    self.faults.pre_launch(self.tick_id, tier, kind, attempt)
                return thunk()
            except (faults_lib.TransientError,
                    jax.errors.JaxRuntimeError) as e:
                if not _is_transient(e):
                    raise
                if self.tracer is not None:
                    self.tracer.instant("launch_retry", tier,
                                        tick=self.tick_id, kind=kind,
                                        attempt=attempt, error=str(e))
                if attempt >= self.launch_retries:
                    raise _RetryExhausted(kind, e) from e
                self.metrics.record_retry(tier)
                attempt += 1
                if delay > 0:
                    time.sleep(delay)
                    delay *= 2

    def _pick_shard(self, tier: int, rt: _TierRuntime,
                    ntokens: int) -> Optional[int]:
        """The data shard the next admission should land on: a shard with
        a free request row whose block pool passes ``can_admit`` for the
        request's first pages, preferring the most free blocks (lowest
        shard id on ties).  None when no shard can take it — single-shard
        tiers degrade to the plain row+block check."""
        alloc = self.scheduler.allocators[tier]
        best, best_free = None, -1
        for s in range(rt.data_shards):
            if alloc.free_in(s) == 0 or not rt.pool.can_admit(ntokens, s):
                continue
            free = rt.pool.blocks.free_in(s)
            if free > best_free:
                best, best_free = s, free
        return best

    def _pick_shard_prefix(self, tier: int, rt: _TierRuntime, req: Request):
        """Chunked admission's shard choice plus the longest cached
        prefix there, as ``(shard, cached_tokens, blocks)``.  Among
        shards with a free row whose pool passes ``can_admit``, prefer
        the longest prefix match, then the most free blocks (lowest
        shard id on ties) — with the cache off this reduces exactly to
        :meth:`_pick_shard`.  A shard whose pool cannot take the request
        *with* its match (the pinned blocks stop being LRU-evictable) is
        retried without it, so caching never blocks an admission the
        uncached path would have made."""
        alloc = self.scheduler.allocators[tier]
        plen = req.prompt_tokens
        best = None
        for s in range(rt.data_shards):
            if alloc.free_in(s) == 0:
                continue
            cached, blocks = (rt.pool.match_prefix(req.prompt, s)
                              if rt.prefix else (0, []))
            span = cached + min(rt.chunk, plen - cached)
            if not rt.pool.can_admit(span, s, cached=cached,
                                     prefix_blocks=blocks):
                if not cached or not rt.pool.can_admit(
                        min(rt.chunk, plen), s):
                    continue
                cached, blocks = 0, []
            key = (cached, rt.pool.blocks.free_in(s), -s)
            if best is None or key > best[0]:
                best = (key, s, cached, blocks)
        if best is None:
            return None, 0, []
        return best[1], best[2], best[3]

    def _trace_req(self, req: Request, state: str,
                   tier: int, shard: Optional[int]) -> None:
        if self.tracer is not None:
            self.tracer.request_transition(req.rid, state, tier, shard,
                                           tick=self.tick_id)

    def _admit(self, tier: int, now: float) -> None:
        """Admission, traced as the tick's ``admit`` phase (both the
        leading and the trailing pass emit one event each)."""
        tr = self.tracer
        if tr is None:
            return self._admit_requests(tier, now)
        t0 = tr.now_us()
        before = self.metrics.tier_requests[tier]
        self._admit_requests(tier, now)
        tr.phase("admit", tier, t0, tick=self.tick_id,
                 admitted=self.metrics.tier_requests[tier] - before)

    def _admit_requests(self, tier: int, now: float) -> None:
        rt = self.runtimes[tier]
        if rt.chunked:
            # mixed-length admission: bind rows one at a time, bounded by
            # free rows, free KV blocks for the *first chunk* (later
            # chunks grow lazily) on the target data shard, and the
            # tier's token budget per tick (scheduler-enforced; the
            # budget window spans both admission passes of a tick via
            # _budget_used, and the window's first admitted request is
            # always admitted so a prompt longer than the whole budget
            # cannot starve).  Unified tiers reason in ONE currency: the
            # window is pre-charged with the tick's carried load (decode
            # tokens + in-flight prefill chunks, see _tick_load) and a
            # new request bills only its first chunk — later chunks
            # occupy later ticks' windows.  Legacy split tiers keep the
            # old accounting (full prompt length, prefill-only window).
            # No compute here — the token batch runs in _tier_step.
            fresh = 0
            while True:
                head = self.scheduler.peek(tier, now)
                if head is None:
                    break
                plen = head.prompt_tokens
                # a preempted request being re-admitted replays work the
                # metrics already counted: don't re-record the admission
                # (Eq 7 cost and stats.requests stay per-request); the
                # replayed compute is visible as replayed_tokens instead
                replay = head.state is RequestState.PREEMPTED
                shard, cached, pblocks = \
                    self._pick_shard_prefix(tier, rt, head)
                if shard is None:
                    break
                # admission billing skips the cached prefix entirely:
                # unified tiers charge the first *uncached* chunk, split
                # tiers the uncached suffix — cached chunks cost 0
                cost = ((lambda r, c=cached:
                         min(rt.chunk, r.prompt_tokens - c))
                        if rt.unified else
                        (lambda r, c=cached: r.prompt_tokens - c)
                        if cached else None)
                reqs, slot_ids = self.scheduler.admit(
                    tier, now, limit=1,
                    token_budget=self.prefill_token_budget,
                    budget_used=self._budget_used[tier],
                    admitted_before=(self._admitted[tier] if rt.unified
                                     else None),
                    token_cost=cost, shard=shard)
                if not reqs:
                    break               # over budget this tick
                req, slot = reqs[0], slot_ids[0]
                rt.pool.bind(slot, cached + min(rt.chunk, plen - cached),
                             row_tokens=plen + self.gen_len,
                             prefix=(cached, pblocks) if cached else None)
                rt.slot_req[slot] = req
                # chunked prefill resumes at the first uncached token
                rt.prefill_pos[slot] = cached
                self._trace_req(req, "PREFILL", tier, shard)
                if rt.prefix:
                    self.metrics.record_prefix_lookup(tier, cached, plen)
                    if self.tracer is not None:
                        self.tracer.prefix_cache_event(
                            tier, req.rid, cached, plen,
                            tick=self.tick_id, shard=shard)
                self._budget_used[tier] += (min(rt.chunk, plen - cached)
                                            if rt.unified
                                            else plen - cached)
                self._admitted[tier] += 1
                fresh += 0 if replay else 1
            if fresh:
                self.metrics.record_admission(tier, fresh)
            return
        if rt.paged:
            # block-aware admission: one request at a time, binding its
            # prompt pages on the picked shard, until rows, blocks, or
            # the queue run out (can_admit leaves the shard's oldest row
            # its worst-case remaining demand — the discipline that makes
            # over-subscription deadlock-free; see serving.slots)
            reqs, slot_ids = [], []
            while self.scheduler.peek(tier, now) is not None:
                shard = self._pick_shard(tier, rt, self.prompt_len)
                if shard is None:
                    break
                r, s = self.scheduler.admit(tier, now, limit=1, shard=shard)
                if not r:
                    break
                rt.pool.bind(s[0], self.prompt_len)
                reqs += r
                slot_ids += s
        else:
            reqs, slot_ids = self.scheduler.admit(tier, now)
        if not reqs:
            return
        self.metrics.record_admission(tier, len(reqs))
        self.metrics.record_prefill_tokens(
            len(reqs) * self.prompt_len, rt.capacity * self.prompt_len)
        tr = self.tracer
        t0 = tr.now_us() if tr is not None else 0.0
        while True:
            prompts = np.zeros((rt.capacity, self.prompt_len), np.int32)
            for i, req in enumerate(reqs):
                prompts[i] = req.prompt
            try:
                with obs.annotation(f"run_prefill/{rt.spec.name}",
                                    self.profile_annotations):
                    part_cache, ftok, fconf = self._launch(
                        tier, "run_prefill",
                        lambda p=prompts: rt.run_prefill(p))
                break
            except _RetryExhausted as e:
                # rows aren't populated yet (slot_req assigns below), so
                # the sacrifice is simple: drop the youngest admission
                # and relaunch the remaining prompts
                req, slot = reqs.pop(), slot_ids.pop()
                req.fail(now)
                if rt.paged:
                    rt.pool.release(slot)
                self.scheduler.release(tier, slot)
                self.metrics.record_failed(tier)
                if tr is not None:
                    tr.request_done(req.rid, tier, None, state="FAILED",
                                    tick=self.tick_id, error=str(e))
                if not reqs:
                    return
        if tr is not None:
            tr.phase("launch", tier, t0, tick=self.tick_id, kind="prefill",
                     width=self.prompt_len)
        self.metrics.record_launches(tier, 1)
        rt.pool.write_prefill(slot_ids, part_cache)
        # one blocking transfer for both outputs (device_get blocks until
        # prefill finished); timestamp tokens with the post-compute clock
        # so TTFT includes prefill, not just queueing (VirtualClock is
        # constant within a step, so ticks are unaffected).  This sync is
        # separate from the tick's coalesced prefill+decode fetch: the
        # uniform one-shot path is the legacy bit-exactness oracle and
        # admits at most twice per tick, not every tick.
        ftok, fconf = self._fetch(tier, (ftok, fconf))
        t_emit = self.clock.now()
        for i, (req, slot) in enumerate(zip(reqs, slot_ids)):
            shard = rt.pool.shard_of(slot) if rt.paged else None
            self._trace_req(req, "PREFILL", tier, shard)
            req.start_decode(t_emit)
            self._trace_req(req, "DECODE", tier, shard)
            req.emit(int(ftok[i]), float(fconf[i]), t_emit)
            rt.slot_req[slot] = req
            rt.tok[slot] = ftok[i]
            rt.pos[slot] = self.prompt_len   # next decode writes here

    def _tick_load(self, rt: _TierRuntime) -> int:
        """Tokens the tier's live rows already claim this tick: one per
        decoding row plus each mid-prefill row's next chunk.  Unified
        admission pre-charges this carried load against the tick's token
        budget — prefill chunks and decode tokens are one currency."""
        load = len(rt.decoding())
        for s in rt.prefilling():
            req = rt.slot_req[s]
            load += min(rt.chunk, req.prompt_tokens - int(rt.prefill_pos[s]))
        return load

    def _build_plan(self, rt: _TierRuntime) -> Optional[StepPlan]:
        """Plan one tier's tick on the host: which rows prefill a chunk,
        which decode a token, which stall — plus the packed token batch
        the launch consumes.  Rows denied KV blocks (over-subscribed
        arena) are marked ``KIND_STALL`` and retry next tick: a stalled
        chunk replays idempotently, a stalled decode row's write lands in
        the null block and its output is discarded (over-subscription is
        rejected at construction for recurrent-state models).  Page
        tables grow lazily here — prefill rows in slot order first, then
        decode rows oldest-bound-first (matching the legacy split launch
        order; deadlock freedom itself comes from the oldest-first
        *reserve* in ``serving/slots.py``, not from this visit order).

        Under the split backend decode rows are only *listed* (their
        stall check, input token, and same-tick first-token fusion live
        in `_exec_split`, preserving the legacy launch order exactly);
        the unified backend consumes the plan verbatim."""
        pre = rt.prefilling() if rt.chunked else []
        dec = rt.decoding()
        dr = rt.draft_slots() if rt.spec_draft else []
        if not pre and not dec and not dr:
            return None
        cap = rt.capacity
        kind = np.zeros(cap, np.int8)
        qlen = np.zeros(cap, np.int32)
        shard = np.zeros(cap, np.int32)
        if rt.paged:
            for s in rt.pool.bound_rows():
                shard[s] = rt.pool.shard_of(s)
        prefill_rows: List[int] = []
        finishing: List[int] = []
        chunks: List[tuple] = []              # (slot, chunk start, length)
        for s in pre:
            req = rt.slot_req[s]
            st = int(rt.prefill_pos[s])
            n = min(rt.chunk, req.prompt_tokens - st)
            if not rt.pool.ensure_blocks(s, st + n - 1):
                kind[s] = KIND_STALL          # replay the chunk next tick
                continue
            kind[s] = KIND_PREFILL
            qlen[s] = n
            prefill_rows.append(s)
            chunks.append((s, st, n))
            if st + n == req.prompt_tokens:
                finishing.append(s)
        decode_rows: List[int] = []
        verify_rows: List[tuple] = []
        draft_rows: List[int] = []
        draft_len = np.zeros(cap, np.int32)
        dentries: List[tuple] = []            # (slot, input tokens, pos0)
        if rt.unified:
            dec_set = set(dec)
            for s in (rt.pool.bound_rows() if rt.paged else dec):
                if s not in dec_set:
                    continue
                req = rt.slot_req[s]
                p = int(rt.pos[s])
                # speculative verify: a decode row with staged drafts
                # scores its next token AND every drafted position in one
                # ragged window (q_len = 1 + nd); its KV writes for
                # rejected positions are provisional — overwritten before
                # ever attended, so rollback needs no block machinery
                nd = 0
                if rt.spec_k and req.draft_tokens:
                    nd = max(0, min(len(req.draft_tokens), rt.spec_k,
                                    self.gen_len - len(req.tokens) - 1))
                if nd > 0 and not rt.pool.ensure_blocks(s, p + nd):
                    # window denied blocks: drop the drafts (the draft
                    # row re-drafts later) and fall back to plain decode
                    req.draft_tokens = []
                    req.draft_confs = []
                    nd = 0
                if nd == 0 and rt.paged and not rt.pool.ensure_blocks(s, p):
                    kind[s] = KIND_STALL      # stall: retry next tick
                    continue
                toks = [int(rt.tok[s])]
                if nd > 0:
                    toks += [int(t) for t in req.draft_tokens[:nd]]
                    verify_rows.append((s, nd))
                kind[s] = KIND_DECODE
                qlen[s] = len(toks)
                decode_rows.append(s)
                dentries.append((s, toks, p))
        else:
            decode_rows = list(dec)
            for s in dec:
                kind[s] = KIND_DECODE
        if rt.spec_draft:
            # draft rows: catch up on the target request's emitted tokens
            # (re-processing them on this cheap tier — the scan's own KV
            # writes are always treated as garbage, so there is zero
            # rollback bookkeeping here), then draft up to spec_k tokens
            # ahead once fully caught up.  Opportunistic: a row denied
            # blocks skips the tick, it never stalls the tier.
            for s in dr:
                req = rt.draft_req[s]
                if req.state is not RequestState.DECODE or req.draft_tokens:
                    continue         # target mid-prefill / drafts pending
                base = req.prompt_tokens
                e = len(req.tokens)
                p0 = int(rt.pos[s])
                c = base + e - p0
                if c <= 0:
                    continue         # caught up; wait for emissions
                n = min(c, rt.chunk)
                kd = 0
                if n == c:           # fully caught up after this chunk
                    kd = max(0, min(rt.spec_k, self.gen_len - e - 1))
                need = max(p0 + n - 1, base + e + kd - 2)
                if not rt.pool.ensure_blocks(s, need):
                    continue
                kind[s] = KIND_DRAFT
                qlen[s] = n
                draft_len[s] = kd
                draft_rows.append(s)
                dentries.append(
                    (s, [int(t) for t in req.tokens[p0 - base:p0 - base + n]],
                     p0))
        # batch width: the chunk when any prefill row survived its block
        # check, else the widest decode/verify/draft row (1 when every
        # row is a plain decode — a tick whose prefill rows ALL stalled
        # decodes at width 1, not chunk width)
        width = rt.chunk if prefill_rows else 1
        if dentries:
            width = max(width, max(len(t) for _, t, _ in dentries))
        tokens = np.zeros((cap, width), np.int32)
        pos = np.zeros((cap, width), np.int32)
        for s, st, n in chunks:
            tokens[s, :n] = rt.slot_req[s].prompt[st:st + n]
            pos[s] = st + np.arange(width)    # row's q_start is pos[s, 0]
        for s, toks, p0 in dentries:
            tokens[s, :len(toks)] = toks
            pos[s] = p0 + np.arange(width)
        flat_width = flat_tokens = flat_pos = q_start = None
        if rt.ragged:
            # flat packing: live tokens of all rows concatenated in slot
            # order, padded up to the smallest bucket width (padding
            # scatters to the null block and emits nothing)
            flat_width = rt.bucket_width(int(qlen.sum()))
            flat_tokens = np.zeros((1, flat_width), np.int32)
            flat_pos = np.zeros((1, flat_width), np.int32)
            q_start = pos[:, 0].astype(np.int32).copy()
            o = 0
            for s in range(cap):
                n = int(qlen[s])
                if n:
                    flat_tokens[0, o:o + n] = tokens[s, :n]
                    flat_pos[0, o:o + n] = pos[s, :n]
                    o += n
        return StepPlan(width=width, kind=kind, tokens=tokens, pos=pos,
                        q_len=qlen, shard=shard, prefill_rows=prefill_rows,
                        decode_rows=decode_rows, finishing=finishing,
                        flat_width=flat_width, flat_tokens=flat_tokens,
                        flat_pos=flat_pos, q_start=q_start,
                        verify_rows=verify_rows, draft_rows=draft_rows,
                        draft_len=draft_len)

    # -- overload: preemption, load shedding, single-request failure --------

    def _pick_victim(self, rt: _TierRuntime, shard: int) -> Optional[int]:
        """The row ``preemption_policy`` evicts on `shard` when the plan
        stalled there.  Never the shard's *oldest* bound row (the
        oldest-first reserve discipline guarantees its progress — that
        guarantee is the termination argument, and it is also why the
        preempt-and-replan loop cannot livelock) and never a row whose
        decode already finished (its work is complete; this tick's gate
        frees it for nothing).  None when no candidate remains."""
        rows = [s for s in rt.pool.bound_rows()
                if rt.pool.shard_of(s) == shard]
        cands = [s for s in rows[1:]
                 if rt.slot_req[s] is not None
                 and not rt.slot_req[s].decode_finished]
        if not cands:
            return None
        if self.preemption_policy == "youngest":
            return cands[-1]
        # fewest-tokens: least total progress (prefilled + decoded);
        # the reverse scan breaks ties toward the youngest binding
        return min(reversed(cands),
                   key=lambda s: int(rt.prefill_pos[s])
                   + len(rt.slot_req[s].tokens))

    def _preempt(self, tier: int, rt: _TierRuntime, slot: int,
                 now: float) -> None:
        """Evict `slot`'s request: discard its partial tier work, free
        its blocks and row, and re-queue it at the *head* of the tier's
        queue.  Re-admission replays prefill and decode from scratch
        through the idempotent chunk machinery; greedy decode is
        deterministic, so the replayed stream is bit-identical (the
        emit-side first_token_time guard keeps TTFT at the original
        emission, matching what a streaming client observed)."""
        req = rt.slot_req[slot]
        shard = rt.pool.shard_of(slot)
        replayed = int(rt.prefill_pos[slot]) + len(req.tokens)
        self._release_draft(req)        # replay restarts decode: any
        req.preempt(now)                # retained draft row is stale
        rt.slot_req[slot] = None
        rt.tok[slot] = 0
        rt.pos[slot] = 0
        rt.prefill_pos[slot] = 0
        rt.pool.release(slot)
        self.scheduler.release(tier, slot)
        self.scheduler.requeue(req, tier)
        self.metrics.record_preemption(tier, replayed)
        self._trace_req(req, "PREEMPTED", tier, shard)

    def _release_draft(self, req: Request) -> None:
        """Free `req`'s retained draft row (if any): the cheap-tier row
        kept alive at escalation to draft tokens for the expensive
        tier's verify pass.  Idempotent; clears any staged drafts so a
        replayed / re-queued request never verifies stale tokens."""
        req.draft_tokens = []
        req.draft_confs = []
        if req.draft_slot is None:
            return
        drt = self.runtimes[req.draft_tier]
        s = req.draft_slot
        drt.draft_req[s] = None
        drt.tok[s] = 0
        drt.pos[s] = 0
        drt.prefill_pos[s] = 0
        if drt.paged:
            drt.pool.release(s)
        self.scheduler.release(req.draft_tier, s)
        req.draft_tier = None
        req.draft_slot = None

    def _preempt_stalled(self, tier: int, rt: _TierRuntime,
                         plan: Optional[StepPlan],
                         now: float) -> Optional[StepPlan]:
        """Trade stalls for evictions: while the plan has stalled rows
        and a stalled shard holds a victim, preempt one row and re-plan.
        Terminates — every pass unbinds a row, and re-planning only ever
        *frees* blocks — and cannot starve the tier, since the shard's
        oldest row is exempt and therefore always progresses."""
        while plan is not None:
            stalled = [s for s in range(rt.capacity)
                       if plan.kind[s] == KIND_STALL]
            if not stalled:
                return plan
            shards = sorted({int(plan.shard[s]) for s in stalled})
            # draft rows first: dropping one costs only speculative
            # work (its target replays nothing), so never preempt a
            # real request while a stalled shard still hosts a draft
            drafts = [s for s in rt.draft_slots()
                      if rt.pool.shard_of(s) in shards]
            if drafts:
                self._release_draft(rt.draft_req[drafts[-1]])
                plan = self._build_plan(rt)
                continue
            victim = None
            for shard in shards:
                victim = self._pick_victim(rt, shard)
                if victim is not None:
                    break
            if victim is None:
                return plan             # nothing evictable: stalls stand
            self._preempt(tier, rt, victim, now)
            plan = self._build_plan(rt)
        return plan

    def _fail_one(self, tier: int, rt: _TierRuntime, rows: Sequence[int],
                  now: float, err: Exception) -> int:
        """Retry exhaustion sacrifices ONE request so the run survives:
        the youngest-bound row among `rows` (highest row on a dense
        arena, whose binding order isn't tracked) fails terminally and
        frees its row and blocks; the caller re-plans and relaunches for
        the survivors.  Returns the victim row."""
        if rt.paged:
            order = {s: i for i, s in enumerate(rt.pool.bound_rows())}
            victim = max(rows, key=lambda s: order.get(s, -1))
        else:
            victim = max(rows)
        req = rt.slot_req[victim]
        shard = rt.pool.shard_of(victim) if rt.paged else None
        self._release_draft(req)
        req.fail(now)
        rt.slot_req[victim] = None
        rt.tok[victim] = 0
        rt.pos[victim] = 0
        rt.prefill_pos[victim] = 0
        if rt.paged:
            rt.pool.release(victim)
        self.scheduler.release(tier, victim)
        self.metrics.record_failed(tier)
        if self.tracer is not None:
            self.tracer.request_done(req.rid, tier, shard, state="FAILED",
                                     tick=self.tick_id, error=str(err))
        return victim

    def _shed(self, tier: int, now: float) -> None:
        """The load-shedding pass (zero-cost when no submitted request
        carries a deadline): reject queued requests of `tier` whose
        deadline has passed or provably cannot be met."""
        if not self._has_deadlines:
            return
        for req in self.scheduler.shed(tier, now, self._service_floor(tier)):
            self._release_draft(req)    # escalated-then-shed requests
            req.shed(now)               # may hold a cheap-tier row
            self.metrics.record_shed(tier)
            if self.tracer is not None:
                self.tracer.request_done(req.rid, tier, None, state="SHED",
                                         tick=self.tick_id)

    def _service_floor(self, tier: int):
        """A per-request lower bound on remaining service time at `tier`
        (None until a tick duration has been observed, so only
        already-expired deadlines shed): minimum ticks to finish —
        ``ceil(prompt/chunk)`` prefill ticks plus ``gen_len - 1`` decode
        ticks, minus one because the final chunk emits the first token in
        its own tick — times the *minimum* observed tick duration.  A
        true lower bound: queue wait, stalls, preemption replays, and
        escalation only add to it."""
        dt = self._min_tick_dt
        if dt is None or dt <= 0:
            return None
        rt = self.runtimes[tier]
        if rt.chunked:
            return lambda r: max(
                math.ceil(r.prompt_tokens / rt.chunk)
                + self.gen_len - 2, 0) * dt
        return lambda r: (self.gen_len - 1) * dt

    def _drain_diagnostics(self) -> str:
        """Per-tier state for the did-not-drain RuntimeError: queue
        depth, live rows, the last plan's stalled rows, and per-shard
        free blocks — enough to tell block starvation from a scheduling
        bug without attaching a debugger."""
        lines = []
        for t, rt in enumerate(self.runtimes):
            line = (f"tier {t} ({rt.spec.name}): "
                    f"queued={len(self.scheduler.queues[t])} "
                    f"live_rows={len(rt.occupied())} "
                    f"stalled_rows={self._last_stalls[t]}")
            if rt.paged:
                shards = range(rt.pool.data_shards)
                line += (" free_blocks_by_shard="
                         f"{[rt.pool.blocks.free_in(s) for s in shards]}")
                held = [rt.pool.blocks.reserved_in(s) for s in shards]
                if any(held):
                    line += f" withheld_by_shard={held}"
                if rt.prefix:
                    line += (" prefix_entries_by_shard="
                             f"{[rt.pool.prefix_index_entries(s) for s in shards]}"
                             " evictable_by_shard="
                             f"{[rt.pool.evictable_in(s) for s in shards]}")
            lines.append(line)
        return "; ".join(lines)

    def _tier_step(self, tier: int, now: float) -> int:
        """One tier's compute for a tick, planned host-side then executed
        by the unified or split backend.  Returns the number of decode
        tokens emitted (the occupancy metric)."""
        rt = self.runtimes[tier]
        tr = self.tracer
        t0 = tr.now_us() if tr is not None else 0.0
        plan = self._build_plan(rt)
        if self.preemption_policy != "none" and rt.chunked:
            plan = self._preempt_stalled(tier, rt, plan, now)
        self._last_stalls[tier] = (
            0 if plan is None else int((plan.kind == KIND_STALL).sum()))
        if plan is not None and tr is not None:
            tr.phase("plan", tier, t0, tick=self.tick_id,
                     width=plan.width,
                     prefill_rows=len(plan.prefill_rows),
                     decode_rows=len(plan.decode_rows),
                     stalled=self._last_stalls[tier])
        if plan is None:
            return 0
        if rt.unified:
            return self._exec_unified(tier, rt, plan, now)
        return self._exec_split(tier, rt, plan, now)

    def _exec_unified(self, tier: int, rt: _TierRuntime,
                      plan: StepPlan, now: float) -> int:
        """Unified token-batch execution: ONE compiled program per tier
        per tick serves every live row — each contributes its next
        prefill chunk or its single decode token (``q_len`` 0/1/chunk
        over the shared page-table gather) — and one blocking
        ``device_get`` fetches every emitted (token, confidence) pair.
        A row finishing prefill this tick emits its first token from the
        batch's last-position logits and starts decoding next tick.
        Mid-prompt-only ticks (nothing to emit) skip the fetch; ticks
        where every live row stalled skip the launch too.  The launch
        sits under the retry wrapper *before* any host state advances:
        replaying it rewrites the same KV pages idempotently, and retry
        exhaustion fails one victim, re-plans, and relaunches for the
        survivors."""
        tr = self.tracer
        use_spec = rt.spec_k > 0 and rt.ragged
        spec_out = None
        while True:
            if not plan.prefill_rows and not plan.decode_rows \
                    and not plan.draft_rows:
                return 0                # every live row stalled
            t0 = tr.now_us() if tr is not None else 0.0
            puts: List[tuple] = []      # traced put interval per attempt
            kind = ("run_spec" if use_spec
                    else "run_ragged" if rt.ragged else "run_mixed")
            try:
                with obs.annotation(f"{kind}/{rt.spec.name}",
                                    self.profile_annotations):
                    if rt.ragged:
                        out = self._launch(
                            tier, kind,
                            lambda p=plan: self._run_flat(rt, p, use_spec,
                                                          puts))
                        tok, conf = out[0], out[1]
                        if use_spec:
                            spec_out = out[2:7]
                        cache = out[-1]
                    else:
                        tok, conf, cache = self._launch(
                            tier, kind,
                            lambda p=plan: rt.run_mixed(p.tokens, p.pos,
                                                        p.q_len))
            except _RetryExhausted as e:
                rows = plan.prefill_rows + plan.decode_rows
                if rows:
                    self._fail_one(tier, rt, rows, now, e)
                else:
                    # a draft-only launch exhausted its retries: drop the
                    # speculation (the targets just decode normally)
                    for s in plan.draft_rows:
                        self._release_draft(rt.draft_req[s])
                plan = self._build_plan(rt)
                if plan is None:
                    return 0
                continue
            rt.pool.cache = cache
            break
        if tr is not None:
            # async dispatch: this phase is host-side launch cost (incl.
            # put_rows transfers); device wait shows under device_get.
            # Each attempt's puts follow as nested ``put`` phases, recorded
            # after the launch so the track's spans stay in start order.
            # The launch ends before its work is counted
            t1 = tr.now_us()
            if rt.ragged:
                tr.phase("launch", tier, t0, t1, tick=self.tick_id,
                         kind="ragged", width=plan.flat_width,
                         **plan.work())
            else:
                tr.phase("launch", tier, t0, t1, tick=self.tick_id,
                         kind="mixed", width=plan.width)
            for p0, p1 in puts:
                tr.phase("put", tier, p0, p1, tick=self.tick_id)
        self.metrics.record_launches(tier, 1)
        # exact live-vs-processed token accounting: the ragged program
        # computes flat_width token slots (bucket padding only), the
        # padded program capacity * width
        self.metrics.record_step_tokens(
            tier, plan.live_tokens,
            plan.flat_width if rt.ragged else rt.capacity * plan.width)
        if plan.prefill_rows:
            # ragged: chunk tokens occupy exactly their live slots; the
            # bucket padding is already charged to wasted_slot_ratio
            self.metrics.record_prefill_tokens(
                plan.live_prefill_tokens,
                plan.live_prefill_tokens if rt.ragged
                else rt.capacity * plan.width)
        # host state advances on host-known lengths only; device outputs
        # stay unfetched until something must be emitted
        for s in plan.prefill_rows:
            rt.prefill_pos[s] += int(plan.q_len[s])
            if rt.prefix:
                # the launch above scattered this chunk's KV: completed
                # chunk boundaries are now publishable prefix entries
                rt.pool.publish_prefix(s, rt.slot_req[s].prompt,
                                       int(rt.prefill_pos[s]))
        t_dec = self.clock.now()
        for s in plan.finishing:
            req = rt.slot_req[s]
            req.start_decode(t_dec)
            self._trace_req(req, "DECODE", tier, int(plan.shard[s]))
            rt.pos[s] = req.prompt_tokens   # next decode writes here
        for s in plan.draft_rows:
            # catch-up advances on host-known lengths, like prefill; the
            # draft scan's own writes beyond this are always re-written
            # by the next catch-up before they could be attended
            rt.pos[s] += int(plan.q_len[s])
        drafting = [s for s in plan.draft_rows if plan.draft_len[s] > 0]
        if not plan.finishing and not plan.decode_rows and not drafting:
            return 0            # mid-prompt chunks / pure catch-up only
        if use_spec:
            fetched = self._fetch(tier, (tok, conf) + tuple(spec_out))
            tok, conf, spec_tok, spec_conf, acc_len, dtok, dconf = fetched
        else:
            tok, conf = self._fetch(tier, (tok, conf))
        t_emit = self.clock.now()       # post-compute (see _admit)
        ver = dict(plan.verify_rows)
        for s in plan.finishing + plan.decode_rows:
            req = rt.slot_req[s]
            nd = ver.get(s, 0)
            if nd:
                # greedy speculative acceptance: emit the scoring model's
                # argmax at every accepted position plus the bonus token —
                # the emitted stream is argmaxes only, bit-identical to
                # non-speculative decode
                acc = min(int(acc_len[s]), nd)
                for j in range(acc + 1):
                    req.emit(int(spec_tok[s, j]), float(spec_conf[s, j]),
                             t_emit)
                rt.tok[s] = int(spec_tok[s, acc])
                rt.pos[s] += acc + 1
                self.metrics.record_speculation(tier, nd, acc)
                # per-token ground-truth agreement for the draft tier's
                # gate: every verified draft up to (and including) the
                # first rejection — past it the drafts' context is
                # already wrong, so the comparison stops being oracle
                for j in range(min(acc + 1, nd)):
                    self.metrics.calibration.record_verify_outcome(
                        tier - 1, float(req.draft_confs[j]), j < acc)
                req.draft_tokens = []
                req.draft_confs = []
            else:
                req.emit(int(tok[s]), float(conf[s]), t_emit)
                rt.tok[s] = tok[s]
        for s in plan.decode_rows:
            if s not in ver:
                rt.pos[s] += 1
        if use_spec and drafting:
            # stage the fetched drafts on their target requests (consumed
            # by the next tier's verify pass later this same tick),
            # truncated at the first token the calibrated gate distrusts
            thr = (self.spec_delta if self.spec_delta is not None
                   else self.scheduler.delta(tier))
            for s in drafting:
                req = rt.draft_req[s]
                dl = int(plan.draft_len[s])
                keep = 0
                for j in range(dl):
                    if float(dconf[s, j]) < thr:
                        break
                    keep += 1
                req.draft_tokens = [int(x) for x in dtok[s, :keep]]
                req.draft_confs = [float(x) for x in dconf[s, :keep]]
        return len(plan.decode_rows)

    def _run_flat(self, rt: _TierRuntime, plan: StepPlan, spec: bool,
                  puts: list):
        """One attempt at a ragged (``spec``: speculative) launch: stage
        the plan's host->device puts, then call the jitted step.  Traced,
        the interval of the puts is appended to ``puts``."""
        tr = self.tracer
        t0 = tr.now_us() if tr is not None else 0.0
        staged = rt.stage_flat(plan.flat_tokens, plan.flat_pos, plan.q_len,
                               plan.q_start, plan.draft_len if spec else None)
        if tr is not None:
            puts.append((t0, tr.now_us()))
        return rt.call_flat(spec, staged)

    def _exec_split(self, tier: int, rt: _TierRuntime,
                    plan: StepPlan, now: float) -> int:
        """Legacy split execution (the ``use_unified_step=False`` escape
        hatch, and the only backend for dense-arena / recurrent-state
        tiers): launch the prefill chunk batch, launch the fused decode
        step — rows whose final chunk completed decode in the same tick,
        their first token flowing into the decode input through a
        device-side ``where`` — then pay a single blocking host sync for
        both result pairs.  Two compiled programs on mixed ticks, which
        is exactly what the unified backend fuses away."""
        pf = None
        tr = self.tracer
        if plan.prefill_rows:
            t0 = tr.now_us() if tr is not None else 0.0
            try:
                with obs.annotation(f"run_chunk/{rt.spec.name}",
                                    self.profile_annotations):
                    tok, conf, cache = self._launch(
                        tier, "run_chunk",
                        lambda: rt.run_chunk(plan.tokens, plan.pos,
                                             plan.q_len))
            except _RetryExhausted as e:
                # fail one victim, re-plan, and restart the tick for the
                # survivors (the failed launch advanced no host state)
                self._fail_one(tier, rt,
                               plan.prefill_rows + plan.decode_rows, now, e)
                plan = self._build_plan(rt)
                if plan is None:
                    return 0
                return self._exec_split(tier, rt, plan, now)
            rt.pool.cache = cache
            if tr is not None:
                tr.phase("launch", tier, t0, tick=self.tick_id,
                         kind="chunk", width=plan.width)
            self.metrics.record_launches(tier, 1)
            self.metrics.record_prefill_tokens(plan.live_prefill_tokens,
                                               rt.capacity * plan.width)
            self.metrics.record_step_tokens(tier, plan.live_prefill_tokens,
                                            rt.capacity * plan.width)
            for s in plan.prefill_rows:
                rt.prefill_pos[s] += int(plan.q_len[s])
                if rt.prefix:
                    rt.pool.publish_prefix(s, rt.slot_req[s].prompt,
                                           int(rt.prefill_pos[s]))
            t_dec = self.clock.now()
            for s in plan.finishing:
                req = rt.slot_req[s]
                req.start_decode(t_dec)
                self._trace_req(req, "DECODE", tier, int(plan.shard[s]))
                rt.pos[s] = req.prompt_tokens   # next decode writes here
            pf = {"tok": tok, "conf": conf, "finished": plan.finishing}
        dc = self._decode_launch(tier, rt, pf, now)
        emit_first = pf is not None and pf["finished"]
        if not emit_first and dc is None:
            return 0
        fetched = self._fetch(tier, (
            (pf["tok"], pf["conf"]) if emit_first else None,
            (dc["tok"], dc["conf"]) if dc is not None else None))
        t_emit = self.clock.now()       # post-compute (see _admit)
        if emit_first:
            ptok, pconf = fetched[0]
            for s in pf["finished"]:
                req = rt.slot_req[s]
                if req is None:
                    continue    # failed mid-tick (decode retry exhaustion)
                req.emit(int(ptok[s]), float(pconf[s]), t_emit)
                rt.tok[s] = ptok[s]
        if dc is None:
            return 0
        ntok, nconf = fetched[1]
        for slot in dc["active"]:
            req = rt.slot_req[slot]
            req.emit(int(ntok[slot]), float(nconf[slot]), t_emit)
            rt.tok[slot] = ntok[slot]
            rt.pos[slot] += 1
        return len(dc["active"])

    def _decode_launch(self, tier: int, rt: _TierRuntime,
                       pf: Optional[dict], now: float) -> Optional[dict]:
        """Launch half of the split backend's fused decode step.  Rows
        whose final prefill chunk completed this tick decode in the same
        tick; their first token is still on device (in ``pf``), so it is
        mixed into the decode input with a device-side ``where`` instead
        of a host round-trip."""
        decoding = rt.decoding()
        if pf is not None and pf["finished"]:
            # rows whose first token is still on device look one emit
            # behind `decode_finished`: drop those the pending prefill
            # emit already completes (gen_len=1), exactly as the old
            # commit-then-decode order did
            decoding = [s for s in decoding
                        if s not in pf["finished"]
                        or len(rt.slot_req[s].tokens) + 1
                        < rt.slot_req[s].gen_len]
        if not decoding:
            return None
        if rt.paged:
            # grow page tables lazily as rows cross block boundaries,
            # oldest row (per data shard) first.  A row denied a block
            # *stalls*: its page stays unmapped (writes hit the null
            # block), its output is discarded, and it retries next tick —
            # attention KV replay is idempotent, and over-subscription is
            # rejected at engine construction for models with recurrent
            # state.
            dec = set(decoding)
            active = [s for s in rt.pool.bound_rows()
                      if s in dec and rt.pool.ensure_blocks(
                          s, int(rt.pos[s]))]
            if not active:
                return None
        else:
            active = decoding
        tok_in = rt.put_rows(rt.tok[:, None])
        if pf is not None and pf["finished"]:
            fresh = np.zeros(rt.capacity, bool)
            fresh[pf["finished"]] = True
            tok_in = jnp.where(rt.put_rows(fresh[:, None]),
                               pf["tok"][:, None].astype(jnp.int32), tok_in)
        # rows mid-prefill share the fused decode batch but must not touch
        # their (bound, partially-filled) pages: mask them to the null
        # block in the decode step's page-table copy
        tr = self.tracer
        while True:
            t0 = tr.now_us() if tr is not None else 0.0
            try:
                with obs.annotation(f"run_step/{rt.spec.name}",
                                    self.profile_annotations):
                    nxt, conf, cache = self._launch(
                        tier, "run_step",
                        lambda: rt.run_step(tok_in,
                                            mask_rows=rt.prefilling()))
            except _RetryExhausted as e:
                # fail one active row and relaunch for the rest: the
                # victim's page-table row is already unmapped, so its
                # residual token in tok_in scatters to the null block
                victim = self._fail_one(tier, rt, active, now, e)
                active = [s for s in active if s != victim]
                if not active:
                    return None
                continue
            rt.pool.cache = cache
            break
        if tr is not None:
            tr.phase("launch", tier, t0, tick=self.tick_id, kind="decode",
                     width=1)
        self.metrics.record_launches(tier, 1)
        self.metrics.record_step_tokens(tier, len(active), rt.capacity)
        return {"active": active, "tok": nxt, "conf": conf}

    def _finish(self, tier: int, now: float) -> None:
        """Gate finished rows, traced as the tick's ``finish`` phase;
        completed *escalated* requests additionally stream their
        escalation outcomes (did the tiers' answers agree?) into the
        calibration telemetry."""
        tr = self.tracer
        if tr is None:
            self._finish_requests(tier, now)
            return
        t0 = tr.now_us()
        done, esc = self._finish_requests(tier, now)
        tr.phase("finish", tier, t0, tick=self.tick_id,
                 completed=done, escalated=esc)

    def _finish_requests(self, tier: int, now: float):
        rt = self.runtimes[tier]
        last = tier == len(self.tiers) - 1
        # fault injection: an escalation storm overrides this gate's
        # decisions for the tick (forced decisions still stream into the
        # gate stats and calibration telemetry like real ones)
        forced = (None if last or self.faults is None
                  else self.faults.force_escalation(self.tick_id, tier))
        done = esc = 0
        for slot in rt.occupied():
            req = rt.slot_req[slot]
            if not (req.state is RequestState.DECODE and req.decode_finished):
                continue
            seq_conf = req.gate(self.conf_reduce)
            if not last and self.scheduler.gate_decision(tier, seq_conf,
                                                         force=forced):
                req.escalate(now)
                self.scheduler.push_escalated(req)
                # span on the *next* tier's track: queued for escalation
                self._trace_req(req, "ESCALATED", tier + 1, None)
                esc += 1
                if self.speculation_k and rt.spec_draft and rt.ragged:
                    # speculative mode: keep this row alive as the
                    # request's draft row — its prompt KV is already
                    # resident, so the cheap tier can catch up on the
                    # expensive tier's emissions and draft ahead.  The
                    # row changes role, not owner: no pool/scheduler
                    # release (the slots invariant checker sees one
                    # binding throughout).
                    self._release_draft(req)    # M>2: drop the older row
                    rt.draft_req[slot] = req
                    rt.slot_req[slot] = None
                    rt.tok[slot] = 0
                    rt.pos[slot] = req.prompt_tokens  # rewind: replay the
                    rt.prefill_pos[slot] = 0          # target's emissions
                    req.draft_tier = tier
                    req.draft_slot = slot
                    continue
            else:
                # post-compute time: the final decode step belongs to this
                # request's latency (`now` was sampled at step start)
                req.complete(self.clock.now())
                self._release_draft(req)
                self.metrics.record_completion(req)
                if req.tier > 0:
                    # escalation outcome: the expensive tier's answer is
                    # in; stream agreement into the reliability bins
                    self.metrics.record_gate_outcomes(req)
                if self.tracer is not None:
                    self.tracer.request_done(
                        req.rid, tier,
                        rt.pool.shard_of(slot) if rt.paged else None,
                        tick=self.tick_id)
                done += 1
            rt.slot_req[slot] = None
            rt.tok[slot] = 0
            rt.pos[slot] = 0
            rt.prefill_pos[slot] = 0
            if rt.paged:
                rt.pool.release(slot)
            self.scheduler.release(tier, slot)
        return done, esc

    def step(self, now: Optional[float] = None) -> None:
        now = self.clock.now() if now is None else now
        self.tick_id += 1
        if self.faults is not None:
            self.faults.begin_tick(self.tick_id, self)
        # minimum observed tick duration: the unit of the shedding pass's
        # min-ticks service-time lower bound (constant dt under a
        # VirtualClock, so the floor is exact there)
        if self._last_tick_t is not None:
            d = now - self._last_tick_t
            if d > 0 and (self._min_tick_dt is None
                          or d < self._min_tick_dt):
                self._min_tick_dt = d
        self._last_tick_t = now
        tr = self.tracer
        tick_t0 = tr.now_us() if tr is not None else 0.0
        # open each tier's token-budget window: unified tiers pre-charge
        # the tick's carried decode+chunk load (one currency), split
        # tiers start the legacy prefill-only window at zero
        self._budget_used = [
            self._tick_load(rt) if rt.unified else 0
            for rt in self.runtimes]
        self._admitted = [0] * len(self.tiers)
        active = []
        # StepTraceAnnotation(step_num=tick_id): the join key between an
        # opt-in jax-profiler device trace and the host tracer's events
        with obs.step_annotation(self.tick_id, self.profile_annotations):
            for tier in range(len(self.tiers)):
                self._shed(tier, now)
                self._admit(tier, now)
                active.append(self._tier_step(tier, now))
                self._finish(tier, now)
            # Trailing admission pass: requests escalated this tick enter
            # the next tier's slots immediately (their decode starts next
            # tick), keeping the invariant `free slot => empty queue` at
            # tick ends.
            for tier in range(len(self.tiers)):
                self._admit(tier, now)
        if tr is not None:
            for t, rt in enumerate(self.runtimes):
                tr.counter(f"queue depth/{rt.spec.name}",
                           len(self.scheduler.queues[t]), tid=t)
                tr.counter(f"live rows/{rt.spec.name}",
                           len(rt.occupied()), tid=t)
            tr.phase("tick", len(self.tiers), tick_t0, tick=self.tick_id,
                     t_engine=now)
        self.metrics.record_step(active, now)
        self.metrics.sync_gate_stats(self.scheduler.gate_stats)

    # -- driver ------------------------------------------------------------

    def _any_occupied(self) -> bool:
        return any(rt.occupied() for rt in self.runtimes)

    def _done(self) -> bool:
        return self.scheduler.pending == 0 and not self._any_occupied()

    def memory_stats(self) -> List[dict]:
        """Per-tier KV arena accounting: block geometry, static arena
        bytes, high-water bytes actually mapped (paged, overall and per
        data shard), and what the dense one-page-per-request arena would
        have allocated."""
        return [dict(tier=rt.spec.name, **rt.pool.memory_stats())
                for rt in self.runtimes]

    def mesh_topology(self) -> List[dict]:
        """Per-tier mesh layout (None entries for unmeshed tiers): axis
        sizes, device count/ids, data shard count, whether params are
        tensor-sharded, and the devices that actually hold the tier's
        params and KV arena — recorded into serving summaries and the
        BENCH json."""

        def held_on(tree):
            return sorted({d.id for x in jax.tree.leaves(tree)
                           for d in x.devices()})

        out = []
        for rt in self.runtimes:
            if rt.mesh is None:
                out.append({"tier": rt.spec.name, "mesh": None,
                            "devices": 1, "data_shards": 1})
                continue
            out.append({
                "tier": rt.spec.name,
                "mesh": {a: int(s) for a, s in
                         zip(rt.mesh.axis_names, rt.mesh.devices.shape)},
                "devices": int(rt.mesh.devices.size),
                "device_ids": [int(d.id) for d in rt.mesh.devices.flat],
                "data_shards": rt.data_shards,
                "shard_params": bool(rt.spec.shard_params),
                "param_device_ids": held_on(rt.params),
                "kv_device_ids": held_on(rt.pool.cache),
            })
        return out

    def compile_stats(self) -> List[dict]:
        """Per-tier compiled-program accounting for the token-batch
        executors: the widths :meth:`warmup` compiled, the widths ticks
        actually launched, and any launched outside the warmed set — a
        mid-run recompile, which the bucketed ragged layout exists to
        eliminate (test-asserted)."""
        out = []
        for rt in self.runtimes:
            mid = sorted(rt.launched_widths - rt.warmed_widths) \
                if rt.warmed_widths else []
            out.append({
                "tier": rt.spec.name,
                "backend": ("ragged" if rt.ragged else
                            "unified" if rt.unified else
                            "split" if rt.chunked else "legacy"),
                "warmed_widths": sorted(rt.warmed_widths),
                "launched_widths": sorted(rt.launched_widths),
                "compiled_programs": len(rt.warmed_widths
                                         | rt.launched_widths),
                "mid_run_recompiles": mid,
            })
        return out

    def reset_clock(self) -> None:
        """Restart the clock at t=0.  Call after compilation / setup and
        before submitting timed requests, so arrival timestamps are
        relative to the start of serving rather than engine construction."""
        self.clock.reset()

    def warmup(self) -> None:
        """Trigger tier compiles before the clock starts: one prefill +
        one decode per tier on dummy data.  The decode's returned cache is
        rebound (step_fn donates its cache input); the
        dummy write lands in the reserved null block (paged: empty page
        tables point at block 0) or at position 0 of free rows (dense),
        neither of which the next occupant ever attends.  Ends by
        resetting the clock so compile time never counts against request
        latency."""
        for rt in self.runtimes:
            if rt.ragged:
                # every bucket width of the one-per-tick ragged program
                # compiles here (q_len all zero: the dummy writes land in
                # the null block), so a mixed-length run never pays a
                # mid-run recompile — compile_stats() asserts this
                zr = np.zeros(rt.capacity, np.int32)
                for w in rt.flat_buckets:
                    z = np.zeros((1, w), np.int32)
                    spec = rt.spec_fn is not None
                    rt.pool.cache = rt.call_flat(spec, rt.stage_flat(
                        z, z, zr, zr, zr if spec else None))[-1]
                rt.warmed_widths = set(rt.flat_buckets)
                rt.launched_widths = set()
                continue
            if rt.unified:
                # both compiled widths of the padded one-per-tick
                # program: the mixed token batch (any prefill row live)
                # and the width-1 decode-only batch
                for w in dict.fromkeys((rt.chunk, 1)):
                    z = np.zeros((rt.capacity, w), np.int32)
                    _, _, rt.pool.cache = rt.run_mixed(
                        z, z, np.zeros(rt.capacity, np.int32))
                rt.warmed_widths = set(dict.fromkeys((rt.chunk, 1)))
                rt.launched_widths = set()
                continue
            if rt.chunked:
                ztok = np.zeros((rt.capacity, rt.chunk), np.int32)
                _, _, rt.pool.cache = rt.run_chunk(
                    ztok, ztok, np.zeros(rt.capacity, np.int32))
            else:
                prompts = np.zeros((rt.capacity, self.prompt_len), np.int32)
                rt.run_prefill(prompts)
            zeros = np.zeros((rt.capacity, 1), np.int32)
            with rt._ctx():
                _, _, rt.pool.cache = rt.step_fn(
                    rt.params, rt.put_rows(zeros), rt.pool.cache,
                    rt.put_rows(zeros), rt.page_table_device())
        self.reset_clock()

    def run(self, max_steps: int = 1_000_000, *,
            metrics_interval: Optional[float] = None,
            on_snapshot=None) -> dict:
        """Drive to completion; returns ``metrics.summary()``.

        ``metrics_interval`` emits a :meth:`ServingMetrics.snapshot`
        dict to ``on_snapshot`` every that-many clock units (seconds, or
        ticks under a VirtualClock) — the streaming view of escalation
        rate, per-gate ECE, and agreement the ``--metrics-interval``
        CLI flag prints as one line per window."""
        steps = 0
        next_snap = (self.clock.now() + metrics_interval
                     if metrics_interval else None)
        while not self._done():
            now = self.clock.now()
            if not self._any_occupied() and not any(
                    self.scheduler.admissible(t, now)
                    for t in range(len(self.tiers))):
                # idle: jump/sleep to the arrival of the queue *head* —
                # admission is FIFO, so the head is what unblocks the queue
                # (min over all arrivals can sit before the head's time and
                # would spin a VirtualClock forever on out-of-order submits)
                nxt = self.scheduler.queues[0][0].arrival_time
                self.clock.wait_until(nxt)
                continue
            self.step(self.clock.now())
            self.clock.step_done()
            steps += 1
            if next_snap is not None and self.clock.now() >= next_snap:
                if on_snapshot is not None:
                    on_snapshot(self.metrics.snapshot(self.clock.now()))
                next_snap = self.clock.now() + metrics_interval
            if steps > max_steps:
                raise RuntimeError(
                    f"engine did not drain after {steps} steps (scheduler "
                    "stuck?): " + self._drain_diagnostics())
        return self.metrics.summary()
