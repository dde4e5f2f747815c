"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets the fake-device XLA flag before
any jax initialization; tests and benches see the real single device).

Every mesh is built with ``AxisType.Auto`` axes: the models place data
with ``logical_constraint`` hints and leave the rest to GSPMD sharding
propagation.  (``jax.make_mesh`` now defaults to ``Explicit`` axes, under
which a gather from a sharded table must name its output sharding.)
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "parse_mesh_shape"]


def _mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model). Multi-pod: 2 pods =
    512 chips (pod, data, model); DP spans (pod, data)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """(data, model) mesh over the first ``data * model`` local devices:
    tests, CPU examples, ``launch/train.py``."""
    return _mesh((data, model), ("data", "model"),
                 jax.devices()[:data * model])


def parse_mesh_shape(text: str):
    """``"2,2"`` -> (2, 2): ``launch/train.py --mesh DATA,MODEL``."""
    parts = [int(p) for p in text.split(",")]
    if len(parts) != 2 or min(parts) < 1:
        raise ValueError(f"--mesh takes DATA,MODEL (e.g. 2,2), got {text!r}")
    return tuple(parts)
