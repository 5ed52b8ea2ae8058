"""Production mesh construction.

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS *before* any jax import; tests
and benches see the single real device).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """Mesh whose axes are all ``Auto``: the sharding rules here place
    arrays with NamedShardings and let GSPMD propagate the rest.  (Under
    JAX's ``Explicit`` axes — what ``jax.make_mesh`` gives by default —
    every gather over a sharded operand would need an ``out_sharding``.)"""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod: 16x16 = 256 chips; multi-pod: 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(shape=(2, 2), axes=None):
    """Small mesh for sharded serving/tests: CPU placeholder devices (the
    host-device override — launch/hostdev.py) or the chips of one host.  2 dims name
    ("data", "model"), 3 name ("pod", "data", "model"), matching the
    production mesh's axis vocabulary so every sharding rule applies."""
    if axes is None:
        axes = ("pod", "data", "model") if len(shape) == 3 \
            else ("data", "model")
    need, have = math.prod(shape), jax.device_count()
    if have < need:
        platform = jax.default_backend()
        hint = (f"launch with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={need} (the "
                f"--mesh entry points set it for you when it is absent)"
                if platform == "cpu" else
                f"this host has {have} {platform} device(s); pick a mesh "
                f"of at most {have}")
        raise RuntimeError(
            f"debug mesh {shape} needs {need} devices but jax sees {have} "
            f"{platform} device(s) — {hint}")
    return _auto_mesh(shape, axes)
