"""Production mesh construction.

Single pod = 16x16 = 256 chips ("data" x "model"); multi-pod adds a leading
"pod" axis (2 x 16 x 16 = 512 chips).  Defined as functions so importing
this module never touches jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first init;
tests and benches must keep seeing 1 device).
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np
import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices=None) -> Mesh:
    """`jax.make_mesh` with every axis ``Auto`` (the compiler propagates
    shardings; `jax.make_mesh` alone makes them ``Explicit``)."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)}; "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512")
    return make_mesh(shape, axes, devices=devices[:need])


def make_debug_mesh(*, multi_pod: bool = False, model: int = 2,
                    data: int = 2) -> Mesh:
    """Tiny mesh with the same axis names (smoke-testing the dry-run)."""
    shape = (2, data, model) if multi_pod else (data, model)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    return make_mesh(shape, axes, devices=jax.devices()[:need])


def parse_mesh_shape(text) -> tuple:
    """``"4x2"`` -> ``(4, 2)``; a bare ``"8"`` -> ``(8, 1)`` (1-D mesh).

    The serve CLI's ``--mesh DxM`` grammar: D data-parallel by M
    model-parallel devices.  Accepts an ``(int, int)`` tuple unchanged.
    """
    if isinstance(text, (tuple, list)):
        parts = [int(p) for p in text]
    else:
        parts = [int(p) for p in
                 str(text).lower().replace("×", "x").split("x") if p != ""]
    if len(parts) == 1:
        parts.append(1)
    if len(parts) != 2 or parts[0] < 1 or parts[1] < 1:
        raise ValueError(
            f"mesh shape must be 'D' or 'DxM' with positive ints, "
            f"got {text!r}")
    return tuple(parts)


def make_vision_mesh(data: Optional[int] = None, model: int = 1) -> Mesh:
    """Vision serving mesh.

    ``model == 1`` (default) keeps the 1-D ``("data",)`` throughput mesh:
    params replicated, only the micro-batch sharded.  ``model > 1`` builds
    the 2-D ``("data", "model")`` latency mesh — the batch still rides
    ``data`` while the per-head QKV stacks and MLP columns split over
    ``model`` (see distributed/sharding.py ``vision_param_specs``).
    ``data`` defaults to every visible device divided by ``model``.
    """
    devices = jax.devices()
    model = max(int(model), 1)
    if data is None:
        data = max(len(devices) // model, 1)
    need = int(data) * model
    if data < 1 or need > len(devices):
        raise RuntimeError(
            f"vision mesh ({data}, {model}) needs {need} devices, found "
            f"{len(devices)}; on CPU run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")
    if model == 1:
        return make_mesh((data,), ("data",), devices=devices[:need])
    return make_mesh((data, model), ("data", "model"),
                     devices=devices[:need])


# TPU v5e hardware constants used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
