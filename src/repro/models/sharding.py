"""Guarded activation-sharding hints.

``shard_hint(x, 'batch', None, 'model')`` applies a
with_sharding_constraint iff a mesh is active (jax.set_mesh) — model code
stays mesh-agnostic and runs unannotated on a single device (smoke tests),
while under the production mesh GSPMD gets the constraints it cannot
infer (the MoE dispatch one-hot chain replicates without them: measured
~490 GB/chip of temp on the kimi-k2 train dry-run, vs ~11 GB with hints).

Logical names: 'batch' -> ('pod','data') axes present in the mesh;
'model' -> 'model'; None -> unsharded.  A dim is only constrained when its
size divides the axis total (uneven dims are left to GSPMD).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import PartitionSpec


def _axis_total(mesh, names):
    return math.prod(dict(mesh.shape)[n] for n in names) if names else 1


def data_axis_size(mesh) -> int:
    """Total data parallelism of ``mesh``: the product of its 'pod' and
    'data' axis sizes (1 for no mesh or a model-only mesh).  The serving
    engine partitions each tier's request rows and KV block pool into
    this many shards."""
    if mesh is None:
        return 1
    sizes = dict(mesh.shape)
    return _axis_total(mesh, [a for a in ("pod", "data") if a in sizes])


def shard_seq_if_heads_unshardable(x, num_heads: int):
    """x [B, T, KV, hd]: shard T over 'model' ONLY when the head dim
    cannot absorb the model axis (kv % model != 0).  With shardable heads
    the default head-parallel layout is already collective-free; forcing a
    T-shard there would just add resharding."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    m = dict(mesh.shape).get("model", 1)
    if m <= 1 or num_heads % m == 0:
        return x
    return shard_hint(x, "batch", "model", None, None)


def shard_hint(x, *spec):
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    sizes = dict(mesh.shape)
    resolved = []
    for dim, s in zip(x.shape, spec):
        if s == "batch":
            axes = tuple(a for a in ("pod", "data") if a in sizes)
            total = _axis_total(mesh, axes)
            if axes and total > 1 and dim % total == 0:
                resolved.append(axes if len(axes) > 1 else axes[0])
            else:
                resolved.append(None)
        elif s == "model":
            if "model" in sizes and sizes["model"] > 1 \
                    and dim % sizes["model"] == 0:
                resolved.append("model")
            else:
                resolved.append(None)
        else:
            resolved.append(None)
    if all(r is None for r in resolved):
        return x
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*resolved))
