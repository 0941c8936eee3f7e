"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state.  Single pod = 16x16 (256 chips, TPU v5e); multi-pod adds
a leading "pod" axis (2 pods = 512 chips), over which only the batch /
fsdp dimensions shard (the pod axis crosses DCN, so we keep per-layer
tensor collectives off it).

Every builder validates the requested shape against the devices that are
actually visible *before* handing the shape to XLA, because
``jax.make_mesh`` on an undersized host raises an opaque reshape error
deep inside device assignment.  The validation error names the CPU
escape hatch (``XLA_FLAGS=--xla_force_host_platform_device_count=N``)
so a failing dry-run or test tells the operator exactly how to proceed.
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with Auto axes: the serving and dry-run paths
    place arrays with ``NamedSharding`` and let GSPMD propagate, which
    the default Explicit axes refuse (``ShardingTypeError`` on gathers)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def _require_devices(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Fail fast, and usefully, when the host cannot back the mesh."""
    need = math.prod(shape)
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"mesh {dict(zip(axes, shape))} needs {need} devices but only "
            f"{have} {'is' if have == 1 else 'are'} visible. On CPU, "
            f"relaunch with XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={need} (set before jax is imported) to simulate the "
            f"mesh, or shrink the requested shape.")


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    _require_devices(shape, axes)
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small ``(data, model)`` mesh over whatever devices exist (CPU
    tests, the serving engine's ``--mesh`` flag)."""
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got "
                         f"data={data} model={model}")
    shape, axes = (data, model), ("data", "model")
    _require_devices(shape, axes)
    return _auto_mesh(shape, axes)
