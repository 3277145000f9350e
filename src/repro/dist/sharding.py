"""Logical-axis sharding: MaxText-style named-rule annotations.

Model code annotates activations with *logical* axis names
(``constrain(x, "batch", "seq", "embed")``); this module maps them to
*mesh* axes through an active rule table installed by :func:`axis_rules`.
Outside any ``axis_rules`` context ``constrain`` is the identity, so the
same model code runs unsharded in unit tests and sharded in the dry-run.

Rules (logical -> mesh axes):

  batch                    -> ("pod", "data")  (whichever exist in the mesh)
  experts / heads / kv_heads /
  mlp / vocab / embed_model -> ("model",)
  seq / embed / frames / None -> replicated

A mesh-axis assignment is dropped per-array when the dimension size is not
divisible by the mesh-axis extent (GSPMD requires divisibility); this keeps
``constrain`` total over every smoke/full shape without per-model casing.
"""
from __future__ import annotations

import contextlib
import math
import threading
from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_STATE = threading.local()

# logical name -> candidate mesh axes (in order; all present ones are used)
_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "experts": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "vocab": ("model",),
    "embed_model": ("model",),
    "seq": (),
    "embed": (),
    "frames": (),
}


def _current_mesh() -> Optional[Mesh]:
    return getattr(_STATE, "mesh", None)


@contextlib.contextmanager
def axis_rules(mesh: Mesh):
    """Install ``mesh`` as the target of logical-axis annotations."""
    prev = _current_mesh()
    _STATE.mesh = mesh
    try:
        yield mesh
    finally:
        _STATE.mesh = prev


def _mesh_axes_for(name: Optional[str], mesh: Mesh) -> tuple[str, ...]:
    if name is None:
        return ()
    cands = _RULES.get(name, ())
    return tuple(a for a in cands if a in mesh.shape)


def _extent(axes: Sequence[str], mesh: Mesh) -> int:
    return math.prod(mesh.shape[a] for a in axes) if axes else 1


def logical_to_spec(
    logical: Sequence[Optional[str]],
    mesh: Mesh,
    shape: Optional[Sequence[int]] = None,
) -> P:
    """PartitionSpec for logical axis names, dropping non-divisible axes."""
    entries = []
    for i, name in enumerate(logical):
        axes = _mesh_axes_for(name, mesh)
        if shape is not None and axes and shape[i] % _extent(axes, mesh):
            axes = ()
        if not axes:
            entries.append(None)
        elif len(axes) == 1:
            entries.append(axes[0])
        else:
            entries.append(tuple(axes))
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Annotate ``x`` with the sharding implied by the logical axis names.

    Identity when no :func:`axis_rules` context is active (unit tests) or
    when the mesh is trivial.
    """
    mesh = _current_mesh()
    if mesh is None or math.prod(mesh.shape.values()) == 1:
        return x
    assert len(logical) == x.ndim, (logical, x.shape)
    spec = logical_to_spec(logical, mesh, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def serve_mesh(devices: Optional[Sequence] = None,
               min_devices: int = 2) -> Optional[Mesh]:
    """Mesh for the batched serving engine: ``devices`` (default: all
    local devices) on one ``data`` axis.

    Returns None on a single device (the engine runs unsharded — the common
    CPU/test case). With devices > 1 the engine traces its decode step and
    head GEMM under ``axis_rules(serve_mesh())``, so every ``batch``-tagged
    activation shards across devices. The entangled kernels cannot be
    partitioned automatically: under the mesh each runs in a ``shard_map``
    on every row against its device's slice of the weight columns
    (``kernels/ops.py``), so all M entanglement groups meet on each device
    and a stream's roll-forward needs nothing from another.
    """
    devs = list(jax.devices() if devices is None else devices)
    if len(devs) < min_devices:
        return None
    return Mesh(np.asarray(devs), ("data",))


def active_mesh() -> Optional[Mesh]:
    """The :func:`axis_rules` mesh when it spans more than one device,
    else None — what kernels that cannot be partitioned automatically
    (Pallas TPU custom calls) check before wrapping themselves in a
    ``shard_map``."""
    mesh = _current_mesh()
    if mesh is None or mesh.size == 1:
        return None
    return mesh


def axis_extent(name: str) -> int:
    """Number of shards the logical axis ``name`` is split into (1 when no
    rule context is active)."""
    mesh = _current_mesh()
    if mesh is None:
        return 1
    return _extent(_mesh_axes_for(name, mesh), mesh)
