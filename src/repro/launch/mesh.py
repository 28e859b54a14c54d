"""Production mesh construction.

Single pod:  (16, 16)    axes ("data", "model")   = 256 chips (TPU v5e pod)
Multi-pod:   (2, 16, 16) axes ("pod", "data", "model") = 512 chips

Defined as functions (not module-level constants) so importing this
module never touches JAX device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any JAX
import; everything else sees the real device count.
"""

from __future__ import annotations

import jax


def _mesh(shape, axes, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axes (the engine's shard_maps carry
    their own specs; jax.make_mesh defaults to Explicit axes)."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto, devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 1
    for s in shape:
        need *= s
    devices = jax.devices()[:need]
    if len(devices) < need:
        raise RuntimeError(
            f"production mesh needs {need} devices, found {len(devices)}; "
            "the dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512")
    return _mesh(shape, axes, devices=devices)


def make_local_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    return _mesh((data, model), ("data", "model"))


def make_graph_mesh(parts: int) -> jax.sharding.Mesh:
    """1D mesh for the graph engine: vertex partitions over all chips."""
    return _mesh((parts,), ("parts",))


def batch_axes(mesh: jax.sharding.Mesh, batch: int):
    """Largest prefix of (pod, data) that divides the batch."""
    axes = [a for a in ("pod", "data") if a in mesh.shape]
    chosen: list[str] = []
    size = 1
    for a in axes:
        if batch % (size * mesh.shape[a]) == 0:
            chosen.append(a)
            size *= mesh.shape[a]
    if not chosen:
        return None
    return tuple(chosen) if len(chosen) > 1 else chosen[0]
