"""Ragged flat token-batch attention (Pallas TPU kernel).

The O(live tokens) form of :mod:`repro.kernels.mixed_attention`.  The
padded mixed kernel gives every row a width-``C`` query slice, so a
decode row (``q_len = 1``) still pays ``C×`` flash work; here the tick's
tokens pack **contiguously** into one flat ``[W]`` axis — row ``b`` owns
flat slots ``[row_start[b], row_start[b] + q_len[b])`` where
``row_start`` is the exclusive prefix sum of ``q_len`` and ``q_len[b]``
is *arbitrary* in ``[0, C]`` (not just ``{0, 1, chunk}``).  ``W`` is the
live-token total padded up to the engine's bucket width, so compute
scales with what is actually live, not ``rows × chunk``.

The grid sweeps flat token **tiles** of ``tile_q`` tokens instead of
rows.  A tile can span several rows (many decode rows pack into one
tile) and a row can span several tiles (a prefill chunk), so the wrapper
flattens the (tile, row) incidence into a **work list** — one grid step
per (tile, owning row) — sorted tile-major so each output tile is
resident for exactly one contiguous span of grid steps:

  grid = (work_items,),   work_items = W/tile_q + B

Each work item walks only the pages its row can attend: from page 0 (or,
under a sliding window, the group holding the oldest key in the window
of the item's first query) to the page of its last in-tile query.  The K/V
pool stays in HBM (``memory_space=pltpu.HBM``); the item fetches its pages
a **group** of ``ppg`` pages at a time (about 128 keys) with one manual
DMA per page into one half of a double buffer ``[2, ppg·bs, KV, hd]``.
While group ``g`` computes, group ``g + 1`` is in flight — and during an
item's last group, group 0 of the next work item, so DMA latency hides
across items too.  Pages past the live range in the last group are not
fetched; their buffer rows hold finite data (zeroed on the first step)
and are masked by key position.  Padding and filler items
(``work_row = -1``) fetch nothing and only zero-write their tile.

All KV heads are handled inside one group: a page's DMA ``(bs, KV, hd)``
spans every head.  The group's rows are copied head-major in VMEM, so
the per-head flash update is a rolled loop over pairs of heads (its
code twice, not KV times: Mosaic's compile time, paid again whenever a
cached program is loaded, grows with the unrolled score blocks).
``work_tile[w]``/``work_row[w]`` are scalar-prefetched
(:class:`pltpu.PrefetchScalarGridSpec`) together with the page table and
the per-row ``row_start``/``q_start``/``q_len`` scalars.  The
online-softmax accumulators (acc, m, l) live in VMEM scratch sized
``[KV, tile_q*G, ...]`` and persist across a tile's whole span of
items: ``work_first``/``work_last`` flags mark the span's edges (init /
normalize-and-write).  Per group, the mask is the intersection of the
tile's flat slots with the owning row's range plus the causal/window
test at the row's absolute positions (``q_start[row] + slot -
row_start[row]``).  int8 KV dequantizes in-kernel exactly as in the
mixed kernel.

``interpret=True`` runs the same body through the Pallas interpreter —
the off-TPU path used by this container and the tests; the jnp oracle
is :func:`repro.kernels.ref.ragged_attention_ref`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
_GROUP_KEYS = 128      # keys a page group aims to hold (one score tile)


def flat_work_layout(q_len, num_tiles: int, tile_q: int):
    """Flatten the (tile, row) incidence of a ragged batch (traced).

    Returns int32 arrays of length ``num_tiles + B``:
      work_tile   owning tile of each work item (tile-major sorted)
      work_row    owning row, or -1 for padding items
      work_first  1 on the first item of each tile (init accumulators)
      work_last   1 on the last item of each tile (normalize + write)
    plus ``row_start`` [B], the exclusive prefix sum of q_len (each
    row's first flat slot).

    Every tile gets at least one item: tiles past ``sum(q_len)`` receive
    a filler so their output block is still zero-written.  A row
    intersects a tile when its flat range overlaps the tile's slots; the
    total incidence count is at most ``num_tiles + B - 1``, so the fixed
    ``num_tiles + B`` work length never truncates.
    """
    i32 = jnp.int32
    q_len = q_len.astype(i32)
    B = q_len.shape[0]
    row_start = jnp.concatenate(
        [jnp.zeros((1,), i32), jnp.cumsum(q_len)])[:B]
    row_end = row_start + q_len
    tile_lo = (jnp.arange(num_tiles, dtype=i32) * tile_q)[:, None]
    inc = ((q_len[None, :] > 0)
           & (row_start[None, :] < tile_lo + tile_q)
           & (row_end[None, :] > tile_lo))                  # [nt, B]
    filler = jnp.sum(inc, axis=1, keepdims=True) == 0       # empty tiles
    mask = jnp.concatenate([inc, filler], axis=1).reshape(-1)
    flat = jnp.arange(num_tiles * (B + 1), dtype=i32)
    # real items keep their tile-major key; non-items sort after them
    order = jnp.argsort(jnp.where(mask, flat, flat + flat.shape[0]))
    sel = order[:num_tiles + B]
    real = jnp.take(mask, sel)
    tile_of = (sel // (B + 1)).astype(i32)
    col = (sel % (B + 1)).astype(i32)
    # padding items tail the last tile (row -1: skipped, never first)
    work_tile = jnp.where(real, tile_of, num_tiles - 1)
    work_row = jnp.where(real & (col < B), col, -1)
    prev = jnp.concatenate([jnp.full((1,), -1, i32), work_tile[:-1]])
    nxt = jnp.concatenate([work_tile[1:], jnp.full((1,), -1, i32)])
    work_first = (work_tile != prev).astype(i32)
    work_last = (work_tile != nxt).astype(i32)
    return work_tile, work_row, work_first, work_last, row_start


def _ragged_kernel(pt_ref, wt_ref, wr_ref, wf_ref, wl_ref, rs_ref,
                   qs_ref, ql_ref, q_ref, hbm, o_ref, acc_ref, m_ref,
                   l_ref, bufs, heads, sem_ref, slot_ref, *, bs: int, TQ: int,
                   KV: int, G: int, ppg: int, items: int, scale: float,
                   window):
    """One work item.  ``hbm``/``bufs``/``heads`` are (k, v[, k_scale,
    v_scale]): the pools in HBM, their ``[2, ppg*bs, ...]`` VMEM double
    buffers and the computing group's head-major copies;
    ``sem_ref[tensor, slot]`` signals a buffer half's DMAs and
    ``slot_ref[0]`` is the half holding the next group to compute."""
    w = pl.program_id(0)
    P = pt_ref.shape[1]
    quant = len(hbm) == 4

    def span(item):
        """The item's row, its first and last live page and its number
        of page groups (0 for padding and filler items)."""
        wr = wr_ref[item]
        row = jnp.maximum(wr, 0)
        start = rs_ref[row]            # row's first flat slot
        qstart = qs_ref[row]           # abs position of that slot's query
        tile_lo = wt_ref[item] * TQ
        hi = jnp.minimum(start + ql_ref[row], tile_lo + TQ)
        last = jnp.minimum((qstart + (hi - 1 - start)) // bs, P - 1)
        first = 0
        if window is not None:
            # the group holding the oldest key in the window of the
            # item's first in-tile query; groups sit on multiples of ppg
            # pages so every query sees the same key blocks whatever its
            # tile (a row's streams stay bit-identical across q_len)
            first_pq = qstart + (jnp.maximum(start, tile_lo) - start)
            first = jnp.maximum(first_pq - window + 1, 0) // (bs * ppg) * ppg
        groups = jnp.where(wr >= 0, (last - first) // ppg + 1, 0)
        return row, first, last, groups

    def dmas(item_span, g, slot, op):
        """Start or wait (``op``) the DMAs of page group ``g`` of an
        item: one per live page and tensor, into buffer half ``slot``."""
        row, first, last, _ = item_span
        p0 = first + g * ppg

        def page(i, carry):
            block = pt_ref[row, p0 + i]
            rows = pl.ds(pl.multiple_of(i * bs, bs), bs)
            for t, (src, buf) in enumerate(zip(hbm, bufs)):
                op(pltpu.make_async_copy(src.at[block], buf.at[slot, rows],
                                         sem_ref.at[t, slot]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(ppg, last - p0 + 1), page, 0)

    def start(item_span, g, slot):
        dmas(item_span, g, slot, lambda c: c.start())

    def wait(item_span, g, slot):
        dmas(item_span, g, slot, lambda c: c.wait())

    cur = span(w)

    @pl.when(w == 0)
    def _prologue():
        for buf in bufs:               # unfetched tails must stay finite
            buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0

        @pl.when(cur[3] > 0)
        def _first_group():
            start(cur, 0, 0)

    @pl.when(wf_ref[w] == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    row, first, _, groups = cur
    nxt = span(jnp.minimum(w + 1, items - 1))
    has_next = (w + 1 < items) & (nxt[3] > 0)
    slot0 = slot_ref[0]
    start_flat = rs_ref[row]
    qstart = qs_ref[row]
    qlen = ql_ref[row]
    wt = wt_ref[w]

    def group(g, carry):
        slot = jax.lax.rem(slot0 + g, 2)

        own_next = g + 1 < groups      # else the next item's group 0

        @pl.when(own_next | has_next)
        def _prefetch():
            start(tuple(jnp.where(own_next, a, b) for a, b in zip(cur, nxt)),
                  jnp.where(own_next, g + 1, 0), 1 - slot)

        wait(cur, g, slot)

        # flat slot / key position masks are head-independent
        shape = (TQ * G, ppg * bs)
        ti = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // G
        tt = wt * TQ + ti                              # flat slot index
        own = (tt >= start_flat) & (tt < start_flat + qlen)
        pq = qstart + (tt - start_flat)                # abs query positions
        t = (first + g * ppg) * bs + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1)
        mask = own & (t <= pq)
        if window is not None:
            mask &= t > pq - window

        # each head's keys and values (and scales) head-major, so the
        # head loop below can be rolled: its code twice, not KV times
        for h in range(KV):
            for t, (buf, hb) in enumerate(zip(bufs, heads)):
                if t >= 2:                 # int8 scales, as a key row
                    hb[h] = buf[slot, :, h][None, :]
                elif KV == 1:              # a one-head pool has no head axis
                    hb[h] = buf[slot]
                else:
                    hb[h] = buf[slot, :, h]

        def one_head(h):
            q = q_ref[:, h].astype(jnp.float32).reshape(TQ * G, -1)
            k = heads[0][h].astype(jnp.float32)           # [ppg*bs, hd]
            v = heads[1][h].astype(jnp.float32)           # [ppg*bs, hd]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            if quant:
                s = s * heads[2][h]                       # fused k dequant
            s = jnp.where(mask, s, _NEG)

            m_old = m_ref[h]                              # [TQ*G, 1]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_old - m_new)
            e = jnp.exp(s - m_new)
            e = jnp.where(mask, e, 0.0)    # fully-masked rows: e would be 1
            l_ref[h] = l_ref[h] * corr + jnp.sum(e, axis=1, keepdims=True)
            if quant:
                e = e * heads[3][h]                       # fused v dequant
            acc_ref[h] = acc_ref[h] * corr + jnp.dot(
                e, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

        per = 2 if KV % 2 == 0 else 1  # two heads' chains overlap

        def step(i, c):
            for j in range(per):
                one_head(i * per + j)
            return c

        jax.lax.fori_loop(0, KV // per, step, 0)
        return carry

    jax.lax.fori_loop(0, groups, group, 0)
    slot_ref[0] = jax.lax.rem(slot0 + groups, 2)

    @pl.when(wl_ref[w] == 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).reshape(
            KV, TQ, G, o_ref.shape[-1]).transpose(1, 0, 2, 3).astype(
                o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "tile_q", "interpret"))
def ragged_attention(q, k_pages, v_pages, page_table, q_start, q_len,
                     *, k_scale=None, v_scale=None, window=None,
                     tile_q: int = 16, interpret: bool = False):
    """One ragged flat-token mixed step over a block-paged KV pool.

    q           [W, KV, G, hd]    flat token-batch queries: row b's
                                  tokens at slots [row_start[b],
                                  row_start[b] + q_len[b]); the tail
                                  past sum(q_len) is bucket padding
    k_pages     [N, bs, KV, hd]   shared KV block pool (f32/bf16 or int8)
    v_pages     [N, bs, KV, hd]
    page_table  [B, P] int32      block id of page j of row b (0 = null)
    q_start     [B]    int32      absolute position of the row's first
                                  query this tick
    q_len       [B]    int32      live queries this tick, any value in
                                  [0, C] (0 = idle row, no flat slots)
    k_scale     [N, bs, KV] f32   per-token dequant scales (int8 pool)
    v_scale     [N, bs, KV] f32
    window      sliding-window size (None = full causal)
    tile_q      flat tokens per grid tile (clamped to W; W must divide
                evenly by the clamped value)

    Every live query's own key must be scattered into the pool before
    the call.  Padding slots (flat index >= sum(q_len)) output zeros.
    Returns [W, KV, G, hd] in q's dtype.
    """
    W, KV, G, hd = q.shape
    B, P = page_table.shape
    bs = k_pages.shape[1]
    TQ = min(tile_q, W)
    if W % TQ:
        raise ValueError(f"flat width {W} not a multiple of tile_q {TQ}")
    nt = W // TQ
    ppg = max(1, min(P, _GROUP_KEYS // bs))      # pages per DMA group

    wt, wr, wf, wl, row_start = flat_work_layout(q_len, nt, TQ)

    def idx_q(w, pt, wt, wr, wf, wl, rs, qs, ql):
        return (wt[w], 0, 0, 0)

    # Mosaic slices a page out of an HBM pool only along whole tiles, so
    # a one-head pool drops its head axis (free: XLA tiles [bs, hd]
    # there) and the int8 scales pad their head axis to whole lanes.
    pools = [a.reshape(a.shape[:2] + (hd,)) if KV == 1 else a
             for a in (k_pages, v_pages)]
    if k_scale is not None:
        lanes = -(-KV // 128) * 128
        pools += [jnp.pad(a, ((0, 0), (0, 0), (0, lanes - KV)))
                  for a in (k_scale, v_scale)]
    bufs = [pltpu.VMEM((2, ppg * bs) + a.shape[2:], a.dtype) for a in pools]
    heads = [pltpu.VMEM((KV, ppg * bs, hd), a.dtype) for a in pools[:2]]
    heads += [pltpu.VMEM((KV, 1, ppg * bs), a.dtype) for a in pools[2:]]

    kernel = functools.partial(
        _ragged_kernel, bs=bs, TQ=TQ, KV=KV, G=G, ppg=ppg, items=nt + B,
        scale=1.0 / math.sqrt(hd), window=window)

    def body(*refs):
        n = len(pools)
        prefetch, (q_ref,), hbm, (o_ref, acc_ref, m_ref, l_ref), rest = (
            refs[:8], refs[8:9], refs[9:9 + n], refs[9 + n:13 + n],
            refs[13 + n:])
        kernel(*prefetch, q_ref, hbm, o_ref, acc_ref, m_ref, l_ref,
               rest[:n], rest[n:2 * n], *rest[2 * n:])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(nt + B,),
        in_specs=[pl.BlockSpec((TQ, KV, G, hd), idx_q)]
        + [pl.BlockSpec(memory_space=pltpu.HBM)] * len(pools),
        out_specs=pl.BlockSpec((TQ, KV, G, hd), idx_q),
        scratch_shapes=[
            pltpu.VMEM((KV, TQ * G, hd), jnp.float32),   # acc
            pltpu.VMEM((KV, TQ * G, 1), jnp.float32),    # running max m
            pltpu.VMEM((KV, TQ * G, 1), jnp.float32),    # running Σexp l
            *bufs,                                       # page groups
            *heads,                                      # ... head-major
            pltpu.SemaphoreType.DMA((len(pools), 2)),
            pltpu.SMEM((1,), jnp.int32),                 # next group's half
        ],
    )
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((W, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ragged_attention",
    )(page_table, wt, wr, wf, wl, row_start,
      q_start.astype(jnp.int32), q_len.astype(jnp.int32), q, *pools)
