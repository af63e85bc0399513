"""Production meshes.

Defined as functions (importing this module never touches jax device state).

Single pod: 16×16 = 256 chips ('data', 'model').
Multi-pod:  2×16×16 = 512 chips ('pod', 'data', 'model') — the 'pod' axis is
the slow (DCN/inter-pod ICI) axis; batch shards over ('pod','data').

``make_host_mesh`` builds a mesh over the *local* host devices — by default
the degenerate 1×1 CPU mesh, but with ``data``/``model`` arguments it forms
a real data×tensor-parallel mesh over forced host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``), which is how the
multi-device test harness proves the sharded fused pipeline on CPU.

``make_abstract_mesh`` mirrors the production shapes as a
``jax.sharding.AbstractMesh`` — enough for every spec-level operation
(``make_rules`` / ``resolve_spec`` / ``tree_shardings``) without 256 devices,
so sharding policies for the full arch zoo are testable anywhere.
"""
from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType

__all__ = ["make_production_mesh", "make_host_mesh", "make_abstract_mesh"]

_POD_SHAPE = (2, 16, 16)
_POD_AXES = ("pod", "data", "model")
_SINGLE_SHAPE = (16, 16)
_SINGLE_AXES = ("data", "model")


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: shardings stay out of the
    array types, so the jitted steps, ``device_put`` layouts and shard_map
    bodies mix freely (Explicit axes, the current default, would make
    mismatched update shardings a type error)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = _POD_SHAPE if multi_pod else _SINGLE_SHAPE
    axes = _POD_AXES if multi_pod else _SINGLE_AXES
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Mesh over local devices: 1×1 by default (CPU tests / examples).

    ``data``/``model`` > 1 require that many visible devices — on CPU that
    means forcing them before the first jax import, e.g.
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for a (2, 4)
    data×tensor-parallel mesh (what ``make test-multidevice`` does).
    """
    return _auto_mesh((data, model), ("data", "model"))


def make_abstract_mesh(*, multi_pod: bool = False) -> AbstractMesh:
    """AbstractMesh twin of :func:`make_production_mesh` (no devices)."""
    shape = _POD_SHAPE if multi_pod else _SINGLE_SHAPE
    axes = _POD_AXES if multi_pod else _SINGLE_AXES
    return AbstractMesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
