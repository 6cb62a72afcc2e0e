"""Paged flash-decode attention (Pallas TPU kernel).

GQA decode over a block-paged KV cache: keys/values live in a shared pool
of fixed-size blocks ``[num_blocks, block_size, KV, hd]`` and each query
row owns a page table ``[max_pages]`` of block ids covering its sequence.
One new token per row attends to its own pages only — decode attention
work is O(Σ per-row live tokens) instead of O(rows · max_seq), and arena
memory is decoupled from ``prompt_len + gen_len``.

Grid = (rows, pages) with the page sweep innermost: the online softmax
accumulators (acc, m, l — the streaming pattern from
``confidence_gate.py``) live in VMEM scratch, one slab per KV head, and
persist across the page sweep of each row.  The page table and per-row
positions are scalar-prefetched (:class:`pltpu.PrefetchScalarGridSpec`)
so the KV block DMA of step ``(b, j)`` is gathered through
``page_table[b, j]`` in the BlockSpec index map — the kernel never sees a
dense ``[rows, max_seq]`` arena.  Each step gathers every KV head of the
page, ``(1, bs, KV, hd)``, and loops over the heads statically, as
:mod:`repro.kernels.ragged_attention` does: a one-head block ``(1, bs,
1, hd)`` would put a dim of 1 second-minor, which the TPU compiler
refuses unless it is the whole KV axis.

Pages past a row's depth are skipped with ``pl.when`` (no FLOPs); their
table entries point at block 0 (the reserved null block) so the gather
stays in-bounds and the pipeline re-fetches a block it already holds.
Sliding windows additionally skip pages that fall entirely behind the
window.  int8 KV is dequantized in-kernel: per-token scales fold into the
score matrix (k) and attention probs (v), so the pool is read at
1 byte/element.

``interpret=True`` runs the same kernel body through the Pallas
interpreter — the path used off-TPU (this container) and by the tests.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _paged_kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref,
                  o_ref, acc_ref, m_ref, l_ref, *, ks_ref, vs_ref,
                  bs: int, KV: int, scale: float, window, np_: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    p = pos_ref[b]
    live = j * bs <= p                     # page starts at or before pos
    if window is not None:
        live &= j * bs + bs - 1 > p - window   # page not wholly behind it

    @pl.when(live)
    def _accumulate():
        for h in range(KV):                # static unroll: plain 2D dots
            q = q_ref[0, h].astype(jnp.float32)            # [G, hd]
            k = k_ref[0, :, h].astype(jnp.float32)         # [bs, hd]
            v = v_ref[0, :, h].astype(jnp.float32)         # [bs, hd]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            if ks_ref is not None:
                s = s * ks_ref[0, :, h][None, :]           # fused k dequant
            t = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = t <= p
            if window is not None:
                mask &= t > p - window
            s = jnp.where(mask, s, _NEG)

            m_old = m_ref[h]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1))
            corr = jnp.exp(m_old - m_new)
            e = jnp.exp(s - m_new[:, None])
            l_ref[h] = l_ref[h] * corr + jnp.sum(e, axis=1)
            if vs_ref is not None:
                e = e * vs_ref[0, :, h][None, :]           # fused v dequant
            acc_ref[h] = acc_ref[h] * corr[:, None] + jnp.dot(
                e, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == np_ - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "interpret"))
def paged_attention(q, k_pages, v_pages, page_table, pos, *,
                    k_scale=None, v_scale=None, window=None,
                    interpret: bool = False):
    """One decode step over a block-paged KV pool.

    q           [B, KV, G, hd]   this step's queries (rows at any depth)
    k_pages     [N, bs, KV, hd]  shared KV block pool (f32/bf16 or int8)
    v_pages     [N, bs, KV, hd]
    page_table  [B, P] int32     block id of page j of row b (0 = null)
    pos         [B]    int32     per-row decode position; keys at t <= pos
                                 are attended (the key at ``pos`` must be
                                 written before the call)
    k_scale     [N, bs, KV] f32  per-token dequant scales (int8 pool only)
    v_scale     [N, bs, KV] f32
    window      sliding-window size (None = full causal)

    Returns [B, KV, G, hd] in q's dtype.
    """
    B, KV, G, hd = q.shape
    N, bs = k_pages.shape[0], k_pages.shape[1]
    P = page_table.shape[1]
    scale = 1.0 / math.sqrt(hd)
    quant = k_scale is not None

    def idx_q(b, j, pt, pp):
        return (b, 0, 0, 0)

    def idx_kv(b, j, pt, pp):
        return (pt[b, j], 0, 0, 0)

    def idx_sc(b, j, pt, pp):
        return (pt[b, j], 0, 0)

    in_specs = [
        pl.BlockSpec((1, KV, G, hd), idx_q),
        pl.BlockSpec((1, bs, KV, hd), idx_kv),
        pl.BlockSpec((1, bs, KV, hd), idx_kv),
    ]
    operands = [q, k_pages, v_pages]
    if quant:
        in_specs += [pl.BlockSpec((1, bs, KV), idx_sc),
                     pl.BlockSpec((1, bs, KV), idx_sc)]
        operands += [k_scale, v_scale]

    kernel = functools.partial(
        _paged_kernel, bs=bs, KV=KV, scale=scale, window=window, np_=P)

    def body(pt_ref, pos_ref, *rest):
        if quant:
            (q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
             acc_ref, m_ref, l_ref) = rest
        else:
            q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = rest
            ks_ref = vs_ref = None
        kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref,
               o_ref, acc_ref, m_ref, l_ref, ks_ref=ks_ref, vs_ref=vs_ref)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, G, hd), idx_q),
        scratch_shapes=[
            pltpu.VMEM((KV, G, hd), jnp.float32),   # acc
            pltpu.VMEM((KV, G), jnp.float32),       # running max m
            pltpu.VMEM((KV, G), jnp.float32),       # running Σexp l
        ],
    )
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(page_table, pos, *operands)
