"""Production mesh construction.

Axes: ("pod", "data", "model"). Single pod = 256 chips (16 x 16);
multi-pod = 2 pods = 512 chips. A FUNCTION (not module-level constant)
so importing never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def mesh_context(mesh: Mesh):
    """``jax.set_mesh(mesh)``: scopes `in/out_shardings` name
    resolution."""
    return jax.set_mesh(mesh)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / CPU smoke)."""
    n = len(jax.devices())
    assert n % model == 0
    return _make_mesh((n // model, model), ("data", "model"))
