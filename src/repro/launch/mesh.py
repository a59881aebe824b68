"""Mesh construction for production pods and local hosts.

Functions (NOT module-level constants) so importing this module never
touches jax device state.  Target hardware: TPU v5e pods — 256 chips/pod,
(16, 16) per pod, 2 pods = 512 chips for the multi-pod mesh.

``make_host_mesh`` builds a mesh over whatever the local host exposes —
including the virtual CPU devices created by
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — which is how
the sharding test-suite and ``benchmarks/bench_sharding.py`` exercise
real 8-way SPMD partitioning on a CPU-only container.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the partitioner
    propagates shardings from the in/out specs, as the model code is
    written for (JAX 0.9 defaults to ``Explicit`` axes)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(tp: Optional[int] = None, data: int = 1, devices=None):
    """A ``(data, tp)`` mesh over the local devices (tests/examples).

    ``tp`` defaults to every local device not claimed by ``data`` —
    so under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    this is a (1, 8) tensor-parallel mesh, and on an ordinary
    single-device host it degrades to the old (1, 1) mesh.  Raises if
    the host cannot cover ``data * tp`` devices (jax.make_mesh checks).
    ``devices`` pins the mesh to those devices (``[jax.devices()[i]]``
    with ``tp=1`` places one fleet replica on chip ``i``).
    """
    n = len(devices) if devices is not None else jax.local_device_count()
    if tp is None:
        tp = max(1, n // max(data, 1))
    return _auto_mesh((data, tp), ("data", "model"), devices)
