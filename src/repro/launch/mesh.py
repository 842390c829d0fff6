"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The production target is TPU v5e:
16x16 = 256 chips per pod, 2 pods = 512 chips for the multi-pod mesh.
"""
from __future__ import annotations

import jax

__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh"]


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axes.  The models place activations by
    sharding constraints and let GSPMD propagate the rest, which Explicit
    axes (the ``jax.make_mesh`` default) refuse, e.g. for the embedding
    gather."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many real devices exist (tests/examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return make_mesh((data, model), ("data", "model"))
