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
per (tile, owning row, page) — sorted tile-major so each output tile is
resident for exactly one contiguous span of grid steps:

  grid = (work_items, pages),   work_items <= W/tile_q + B

All KV heads are handled inside one grid step (a static unrolled loop
with per-head accumulators) instead of a third grid dimension: the KV
block gather ``(1, bs, KV, hd)`` spans every head of the page, which
keeps the step count — the dominant cost both for TPU grid dispatch and
for the interpreter — at ``work_items × pages``.

``work_tile[w]``/``work_row[w]`` are scalar-prefetched
(:class:`pltpu.PrefetchScalarGridSpec`) together with the page table and
the per-row ``row_start``/``q_start``/``q_len`` scalars, so grid step
``(w, j)`` gathers KV block ``page_table[work_row[w], j]`` in the
BlockSpec index map.  The online-softmax accumulators (acc, m, l) live
in VMEM scratch sized ``[KV, tile_q*G, ...]`` and persist across a
tile's whole (row, page) span: ``work_first``/``work_last`` flags mark
the span's edges (init / normalize-and-write).  Tiles past the live
total get one padding work item (``work_row = -1``) so their output
still zero-fills.  Per step, the mask is the intersection of the tile's
flat slots with the owning row's range plus the causal/window test at
the row's absolute positions (``q_start[row] + slot - row_start[row]``).
Pages past the row's last in-tile query, pages wholly behind the
sliding window, and padding items are ``pl.when``-skipped (no FLOPs).
int8 KV dequantizes in-kernel exactly as in the mixed kernel.

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
                   qs_ref, ql_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, ks_ref, vs_ref,
                   bs: int, TQ: int, KV: int, G: int, scale: float,
                   window, np_: int):
    w = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((wf_ref[w] == 1) & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    wt = wt_ref[w]
    wr = wr_ref[w]
    row = jnp.maximum(wr, 0)
    start = rs_ref[row]                # row's first flat slot
    qstart = qs_ref[row]               # abs position of that slot's query
    qlen = ql_ref[row]
    lo = jnp.maximum(start, wt * TQ)   # row ∩ tile flat range
    hi = jnp.minimum(start + qlen, wt * TQ + TQ)
    last_pq = qstart + (hi - 1 - start)    # abs pos of last in-tile query
    live = (wr >= 0) & (j * bs <= last_pq)
    if window is not None:
        # first in-tile query's window lower bound; later queries see more
        first_pq = qstart + (lo - start)
        live &= j * bs + bs - 1 > first_pq - window

    @pl.when(live)
    def _accumulate():
        # flat slot / key position masks are head-independent
        shape = (TQ * G, bs)
        ti = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // G
        tt = wt * TQ + ti                              # flat slot index
        own = (tt >= start) & (tt < start + qlen)
        pq = qstart + (tt - start)                     # abs query positions
        t = j * bs + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = own & (t <= pq)
        if window is not None:
            mask &= t > pq - window

        for h in range(KV):            # static unroll: plain 2D dots
            q = q_ref[:, h].astype(jnp.float32).reshape(TQ * G, -1)
            k = k_ref[0, :, h].astype(jnp.float32)     # [bs, hd]
            v = v_ref[0, :, h].astype(jnp.float32)     # [bs, hd]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            if ks_ref is not None:
                s = s * ks_ref[0, :, h][None, :]       # fused k dequant
            s = jnp.where(mask, s, _NEG)

            m_old = m_ref[h]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1))
            corr = jnp.exp(m_old - m_new)
            e = jnp.exp(s - m_new[:, None])
            e = jnp.where(mask, e, 0.0)    # fully-masked rows: e would be 1
            l_ref[h] = l_ref[h] * corr + jnp.sum(e, axis=1)
            if vs_ref is not None:
                e = e * vs_ref[0, :, h][None, :]       # fused v dequant
            acc_ref[h] = acc_ref[h] * corr[:, None] + jnp.dot(
                e, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when((wl_ref[w] == 1) & (j == np_ - 1))
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[..., None]
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
    scale = 1.0 / math.sqrt(hd)
    quant = k_scale is not None

    wt, wr, wf, wl, row_start = flat_work_layout(q_len, nt, TQ)

    def idx_q(w, j, pt, wt, wr, wf, wl, rs, qs, ql):
        return (wt[w], 0, 0, 0)

    def idx_kv(w, j, pt, wt, wr, wf, wl, rs, qs, ql):
        return (pt[jnp.maximum(wr[w], 0), j], 0, 0, 0)

    def idx_sc(w, j, pt, wt, wr, wf, wl, rs, qs, ql):
        return (pt[jnp.maximum(wr[w], 0), j], 0, 0)

    in_specs = [
        pl.BlockSpec((TQ, KV, G, hd), idx_q),
        pl.BlockSpec((1, bs, KV, hd), idx_kv),
        pl.BlockSpec((1, bs, KV, hd), idx_kv),
    ]
    operands = [q, k_pages, v_pages]
    if quant:
        in_specs += [pl.BlockSpec((1, bs, KV), idx_sc),
                     pl.BlockSpec((1, bs, KV), idx_sc)]
        operands += [k_scale, v_scale]

    kernel = functools.partial(
        _ragged_kernel, bs=bs, TQ=TQ, KV=KV, G=G, scale=scale,
        window=window, np_=P)

    def body(pt_ref, wt_ref, wr_ref, wf_ref, wl_ref, rs_ref, qs_ref,
             ql_ref, *rest):
        if quant:
            (q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
             acc_ref, m_ref, l_ref) = rest
        else:
            q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = rest
            ks_ref = vs_ref = None
        kernel(pt_ref, wt_ref, wr_ref, wf_ref, wl_ref, rs_ref, qs_ref,
               ql_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
               l_ref, ks_ref=ks_ref, vs_ref=vs_ref)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(nt + B, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((TQ, KV, G, hd), idx_q),
        scratch_shapes=[
            pltpu.VMEM((KV, TQ * G, hd), jnp.float32),   # acc
            pltpu.VMEM((KV, TQ * G), jnp.float32),       # running max m
            pltpu.VMEM((KV, TQ * G), jnp.float32),       # running Σexp l
        ],
    )
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((W, KV, G, hd), q.dtype),
        interpret=interpret,
        name="ragged_attention",
    )(page_table, wt, wr, wf, wl, row_start,
      q_start.astype(jnp.int32), q_len.astype(jnp.int32), *operands)
