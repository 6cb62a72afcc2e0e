"""Fused cascade confidence gate (Pallas TPU kernel).

The paper's gate is `conf = max softmax(logits)` compared against δ.  At
LLM vocab sizes (up to 262k here) a naive implementation materializes the
full softmax: three HBM passes over the logits.  This kernel computes, in
ONE streaming pass over vocab tiles held in VMEM:

    * conf     = max softmax probability        (the paper's score)
    * entropy  = H(p)                           (alternative score)
    * argmax   = top-1 token id
    * logz     = logsumexp (for downstream temperature re-scaling)

using online-softmax accumulators (running max m, Σexp S, Σ(x-m)exp T):

    logZ = m + log S;  conf = exp(x_max - logZ);  H = logZ - (m + T/S)

Grid: (row_tiles, vocab_tiles), vocab innermost => the VMEM scratch
accumulators persist across the vocab sweep of each row tile (TPU grids
execute sequentially per core).  Tiles are (8, 1024): 8 sublanes x 8*128
lanes, 32 KiB of VMEM per tile at f32.

Every block is 2-D, as the TPU compiler requires once there is more than
one row tile: the accumulators are ``(8, 1)`` column slabs and each
output is a lane-dense ``(8, 128)`` slab per row tile holding the row's
value in every lane (the wrapper keeps lane 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 8
VOCAB_TILE = 1024
LANES = 128          # lane width of each output slab
_NEG = -1e30


def _gate_kernel(x_ref, conf_ref, ent_ref, arg_ref, logz_ref,
                 m_ref, s_ref, t_ref, amax_ref, aidx_ref, *, nv: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        s_ref[...] = jnp.zeros_like(s_ref)
        t_ref[...] = jnp.zeros_like(t_ref)
        amax_ref[...] = jnp.full_like(amax_ref, _NEG)
        aidx_ref[...] = jnp.zeros_like(aidx_ref)

    x = x_ref[...].astype(jnp.float32)                     # [R, VT]
    tile_max = jnp.max(x, axis=1, keepdims=True)           # [R, 1]
    tile_arg = jnp.argmax(x, axis=1, keepdims=True).astype(
        jnp.int32) + j * x.shape[1]

    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, tile_max)
    corr = jnp.exp(m_old - m_new)                          # rescale factor
    e = jnp.exp(x - m_new)
    s_old = s_ref[...]
    s_ref[...] = s_old * corr + jnp.sum(e, axis=1, keepdims=True)
    # re-center the Σ(x-m)e accumulator onto the new max:
    #   Σ(x-m_new)e^{x-m_new} = corr·[T_old + (m_old-m_new)·S_old] + tile term
    t_ref[...] = corr * (t_ref[...] + (m_old - m_new) * s_old) \
        + jnp.sum((x - m_new) * e, axis=1, keepdims=True)
    m_ref[...] = m_new

    upd = tile_max > amax_ref[...]
    amax_ref[...] = jnp.where(upd, tile_max, amax_ref[...])
    aidx_ref[...] = jnp.where(upd, tile_arg, aidx_ref[...])

    @pl.when(j == nv - 1)
    def _finish():
        m = m_ref[...]
        s = s_ref[...]
        logz = m + jnp.log(s)
        lanes = conf_ref.shape

        def put(ref, v):
            ref[...] = jnp.broadcast_to(v, lanes)

        put(conf_ref, jnp.exp(amax_ref[...] - logz))
        put(ent_ref, jnp.log(s) - t_ref[...] / s)         # logZ - E[x-m]... see note
        put(arg_ref, aidx_ref[...])
        put(logz_ref, logz)


# note: H = logZ - E[x] = (m + log S) - (m + T/S) = log S - T/S.


@functools.partial(jax.jit, static_argnames=("interpret",))
def confidence_gate(logits, *, interpret: bool = False):
    """logits [..., V] -> dict(conf, entropy, argmax, logz), each [...]."""
    orig_shape = logits.shape[:-1]
    V = logits.shape[-1]
    x = logits.reshape(-1, V)
    R = x.shape[0]

    rpad = (-R) % ROW_TILE
    vpad = (-V) % VOCAB_TILE
    if rpad or vpad:
        x = jnp.pad(x, ((0, rpad), (0, vpad)), constant_values=_NEG)
    Rp, Vp = x.shape
    nr, nv = Rp // ROW_TILE, Vp // VOCAB_TILE

    out_shapes = (
        jax.ShapeDtypeStruct((Rp, LANES), jnp.float32),   # conf
        jax.ShapeDtypeStruct((Rp, LANES), jnp.float32),   # entropy
        jax.ShapeDtypeStruct((Rp, LANES), jnp.int32),     # argmax
        jax.ShapeDtypeStruct((Rp, LANES), jnp.float32),   # logz
    )
    row_spec = pl.BlockSpec((ROW_TILE, LANES), lambda i, j: (i, 0))
    conf, ent, arg, logz = pl.pallas_call(
        functools.partial(_gate_kernel, nv=nv),
        grid=(nr, nv),
        in_specs=[pl.BlockSpec((ROW_TILE, VOCAB_TILE), lambda i, j: (i, j))],
        out_specs=(row_spec, row_spec, row_spec, row_spec),
        out_shape=out_shapes,
        scratch_shapes=[
            # m, s, t, amax (f32) + aidx (i32), one slot per row in tile
            pltpu.VMEM((ROW_TILE, 1), jnp.float32),
            pltpu.VMEM((ROW_TILE, 1), jnp.float32),
            pltpu.VMEM((ROW_TILE, 1), jnp.float32),
            pltpu.VMEM((ROW_TILE, 1), jnp.float32),
            pltpu.VMEM((ROW_TILE, 1), jnp.int32),
        ],
        interpret=interpret,
        name="confidence_gate",
    )(x)

    def cut(a):
        return a[:R, 0].reshape(orig_shape)

    return {"conf": cut(conf), "entropy": cut(ent),
            "argmax": cut(arg), "logz": cut(logz)}
