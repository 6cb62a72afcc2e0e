"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True on the CPU backend, so the same call sites
run the kernel bodies in Python there (the tests), and to False on TPU,
where they compile to Mosaic.  Any other backend is an error rather than
a silent fallback to interpretation.

Under a multi-device mesh (a serving tier's mesh, activated with
``jax.set_mesh``) the serving kernels run inside ``shard_map``: the TPU
compiler cannot partition a Mosaic kernel itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.confidence_gate import confidence_gate as _gate
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.mamba_scan import mamba_scan as _mamba
from repro.kernels.mixed_attention import mixed_attention as _mixed
from repro.kernels.paged_attention import paged_attention as _paged
from repro.kernels.ragged_attention import ragged_attention as _ragged
from repro.kernels.prefill_attention import \
    paged_prefill_attention as _paged_prefill
from repro.kernels.router_gate import router_gate as _router
from repro.kernels.rwkv6_scan import rwkv6_scan as _rwkv


def _default_interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels run compiled on tpu or interpreted on cpu; "
            f"backend {backend!r} is neither")
    return backend == "cpu"


def _multi_device_mesh():
    """The mesh the caller is traced under, or None on one device."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty or mesh.size == 1 else mesh


def confidence_gate(logits, *, interpret=None):
    gate = functools.partial(_gate, interpret=_default_interpret()
                             if interpret is None else interpret)
    mesh = _multi_device_mesh()
    if mesh is None:
        return gate(logits)
    # every device of the mesh gates the whole (replicated) batch
    return jax.shard_map(gate, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(logits)


def spec_accept(argmax_w, conf_w, q_len, flat_tokens, k):
    """Fused accept/reject epilogue for speculative cascade verify.

    Consumes the per-position picks of a flat verify pass — ``argmax_w``
    / ``conf_w`` shaped [W], the per-flat-slot argmax token and
    max-softmax-prob confidence (from :func:`confidence_gate` over the
    ``[W, V]`` logits of ``transformer.ragged_verify``, or the jnp
    fallback) — plus the ragged layout (``q_len [R]``, the launch's
    ``flat_tokens [1, W]``) and the static draft bound ``k``, and
    decides acceptance device-side so the engine still pays ONE
    ``device_get`` per tier per tick:

    * ``tok``/``conf`` [R] — each row's last-live-slot pick, the exact
      contract of the non-speculative ragged step (the gate is
      per-position, so gating all W slots then gathering equals
      gathering then gating).
    * ``spec_tok``/``spec_conf`` [R, k+1] — the row's window of picks
      starting at its first flat slot: position j is the scoring model's
      argmax after consuming drafted token j (j=0 consumes the row's
      last emitted token).
    * ``acc_len`` [R] — accepted draft count: the longest prefix where
      slot j's argmax equals the *next* drafted token in the flat batch
      (``flat_tokens[start + j + 1]``), greedy speculative decoding's
      acceptance rule.  Rows with ``q_len <= 1`` (no drafts) get 0.

    Emitted tokens are always ``spec_tok[:acc_len + 1]`` — scoring-model
    argmaxes, never drafts — so streams are bit-identical to the
    non-speculative oracle at any k.
    """
    w = argmax_w.shape[0]
    csum = jnp.cumsum(q_len)
    last = jnp.clip(csum - 1, 0, w - 1)
    start = csum - q_len
    idx = start[:, None] + jnp.arange(k + 1, dtype=q_len.dtype)[None, :]
    spec_tok = argmax_w[jnp.clip(idx, 0, w - 1)].astype(jnp.int32)
    spec_conf = conf_w[jnp.clip(idx, 0, w - 1)]
    drafted = flat_tokens[0][jnp.clip(idx + 1, 0, w - 1)]
    valid = jnp.arange(k + 1)[None, :] < (q_len - 1)[:, None]
    match = (spec_tok == drafted) & valid
    acc_len = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    return {"tok": argmax_w[last].astype(jnp.int32), "conf": conf_w[last],
            "spec_tok": spec_tok, "spec_conf": spec_conf,
            "acc_len": acc_len}


def flash_attention(q, k, v, *, causal=True, window=None, interpret=None):
    return _flash(q, k, v, causal=causal, window=window,
                  interpret=_default_interpret()
                  if interpret is None else interpret)


def paged_attention(q, k_pages, v_pages, page_table, pos, *,
                    k_scale=None, v_scale=None, window=None, interpret=None):
    return _paged(q, k_pages, v_pages, page_table, pos,
                  k_scale=k_scale, v_scale=v_scale, window=window,
                  interpret=_default_interpret()
                  if interpret is None else interpret)


def paged_prefill_attention(q, k_pages, v_pages, page_table, q_start, q_len,
                            *, k_scale=None, v_scale=None, window=None,
                            interpret=None):
    return _paged_prefill(q, k_pages, v_pages, page_table, q_start, q_len,
                          k_scale=k_scale, v_scale=v_scale, window=window,
                          interpret=_default_interpret()
                          if interpret is None else interpret)


def mixed_attention(q, k_pages, v_pages, page_table, q_start, q_len, *,
                    k_scale=None, v_scale=None, window=None,
                    interpret=None):
    return _mixed(q, k_pages, v_pages, page_table, q_start, q_len,
                  k_scale=k_scale, v_scale=v_scale, window=window,
                  interpret=_default_interpret()
                  if interpret is None else interpret)


def ragged_attention(q, k_pages, v_pages, page_table, q_start, q_len, *,
                     k_scale=None, v_scale=None, window=None,
                     tile_q=16, interpret=None):
    kernel = functools.partial(
        _ragged, window=window, tile_q=tile_q,
        interpret=_default_interpret() if interpret is None else interpret)
    mesh = _multi_device_mesh()
    if mesh is None:
        return kernel(q, k_pages, v_pages, page_table, q_start, q_len,
                      k_scale=k_scale, v_scale=v_scale)
    return _ragged_on_data_shards(kernel, mesh, q, k_pages, v_pages,
                                  page_table, q_start, q_len,
                                  k_scale, v_scale)


def _ragged_on_data_shards(kernel, mesh, q, k_pages, v_pages, page_table,
                           q_start, q_len, k_scale, v_scale):
    """The ragged kernel on a tier mesh, one call per data shard.

    A data shard owns a contiguous range of engine rows and the
    contiguous range of KV blocks those rows use
    (:class:`repro.serving.slots.TierSlotPool`), and the flat batch packs
    rows in order, so shard ``s``'s tokens are one contiguous flat span
    starting after every earlier shard's tokens.  Each shard rolls its
    span to the front, attends its own rows over its own blocks (page
    ids made shard-local), and rolls the result back; a ``psum`` over the
    data axes assembles the flat output, since every slot has one owner
    and the other shards contribute zeros.  Any 'model' axis runs the
    kernel replicated.
    """
    data = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    quant = k_scale is not None

    def body(q, kp, vp, pt, qs, ql, ql_all, *scales):
        shard = jax.lax.axis_index(data)
        rows = jnp.arange(ql_all.shape[0])
        offset = jnp.sum(jnp.where(rows < shard * pt.shape[0], ql_all, 0))
        ks, vs = scales if quant else (None, None)
        out = kernel(jnp.roll(q, -offset, axis=0), kp, vp,
                     jnp.maximum(pt - shard * kp.shape[0], 0), qs, ql,
                     k_scale=ks, v_scale=vs)
        return jax.lax.psum(jnp.roll(out, offset, axis=0), data)

    pool, rows = P(data), P(data)
    in_specs = (P(), pool, pool, rows, rows, rows, P()) \
        + ((pool, pool) if quant else ())
    scales = (k_scale, v_scale) if quant else ()
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=P(),
                         check_vma=False)(
        q, k_pages, v_pages, page_table, q_start, q_len, q_len, *scales)


def rwkv6_scan(r, k, v, w, u, *, interpret=None):
    return _rwkv(r, k, v, w, u, interpret=_default_interpret()
                 if interpret is None else interpret)


def mamba_scan(x, dt, B_t, C_t, A, *, interpret=None):
    return _mamba(x, dt, B_t, C_t, A, interpret=_default_interpret()
                  if interpret is None else interpret)


def router_gate(logits, k, *, interpret=None):
    return _router(logits, k, interpret=_default_interpret()
                   if interpret is None else interpret)
