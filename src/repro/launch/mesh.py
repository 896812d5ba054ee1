"""Device meshes.  Defined as FUNCTIONS so importing this module never
touches jax device state (device count is locked at first jax init).

Every mesh uses ``AxisType.Auto`` axes: shardings are propagated by GSPMD
from the ``NamedSharding`` annotations the model and the kernel wrappers
place, not carried in the types.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType

from repro.sharding.rules import MeshRules


def make_mesh(shape=(2, 2), axes=("data", "model")) -> jax.sharding.Mesh:
    """A mesh over the first ``prod(shape)`` local devices.  Raises when
    fewer devices exist (on CPU, force more first with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)."""
    n, have = math.prod(shape), len(jax.devices())
    if have < n:
        raise ValueError(f"mesh {dict(zip(axes, shape))} needs {n} devices, "
                         f"{have} visible")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=jax.devices()[:n])


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_rules(*, multi_pod: bool = False, fsdp: bool = False) -> MeshRules:
    dp = ("pod", "data") if multi_pod else ("data",)
    return MeshRules(model="model", dp=dp, fsdp=("data",) if fsdp else None)
