"""Production meshes.

Target hardware: TPU v5e pods — 256 chips per pod, 2 pods for the
multi-pod configuration.  Axes:

  * ``data``  — batch (and, for batch=1 long-context, KV-cache sequence)
  * ``model`` — tensor/expert parallelism
  * ``pod``   — data parallelism across pods (multi-pod only)

Defined as functions (never module-level constants) so importing this
module touches no jax device state.
"""
from __future__ import annotations

import jax

# TPU v5e hardware constants (per chip) — used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link


def _mk(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_test_mesh(devices: int = 8):
    """Small mesh for CI-style dry-run tests (host platform devices)."""
    if devices % 4 == 0:
        return _mk((devices // 4, 4), ("data", "model"))
    return _mk((1, devices), ("data", "model"))


def make_tier_mesh(data: int = 1, model: int = 1, devices=None):
    """A ('data','model') mesh for one cascade tier.

    Multi-tier serving gives each tier its own mesh over a *subset* of
    the host's devices (the heavy tier typically gets more chips), so
    unlike :func:`make_test_mesh` this accepts an explicit device list.
    With ``devices=None`` and ``data*model`` covering every local device
    it defers to :func:`_mk`; otherwise it builds the Mesh over the given
    slice directly.
    """
    import numpy as np
    shape, axes = (data, model), ("data", "model")
    if devices is None:
        devices = jax.devices()
        if data * model == len(devices):
            return _mk(shape, axes)
        devices = devices[:data * model]
    if len(devices) != data * model:
        raise ValueError(f"tier mesh {data}x{model} needs {data * model} "
                         f"devices, got {len(devices)}")
    return jax.sharding.Mesh(np.asarray(devices).reshape(shape), axes)


def make_tier_meshes(shapes, devices=None):
    """One mesh per cascade tier from ``[(data, model), ...]`` shapes.

    Devices are assigned contiguously from ``jax.devices()`` so tiers
    occupy disjoint chip sets when they fit side by side (tier 0 on the
    first ``d0*m0`` chips, tier 1 on the next ``d1*m1``, ...); when a
    tier would run past the end, assignment wraps to device 0 and tiers
    share chips (JAX multiplexes fine on a single host).
    """
    devs = list(jax.devices()) if devices is None else list(devices)
    meshes, off = [], 0
    for data, model in shapes:
        n = data * model
        if n > len(devs):
            raise ValueError(f"tier mesh {data}x{model} needs {n} devices, "
                             f"only {len(devs)} available")
        if off + n > len(devs):
            off = 0                       # wrap: tiers share devices
        meshes.append(make_tier_mesh(data, model, devs[off:off + n]))
        off += n
    return meshes


def num_chips(mesh) -> int:
    return mesh.devices.size
