"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
does not touch jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization.

Every mesh has Auto axes: the model and the optimizer place arrays through
GSPMD sharding constraints (``sharding/rules.py``), which Explicit axes —
``jax.make_mesh``'s default — reject outside a ``jax.set_mesh`` context.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """Arbitrary Auto-axis mesh (tests, small-scale runs, described
    devices for a compile rehearsal)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally, as a 1-D 'data' mesh."""
    return make_mesh((len(jax.devices()),), ("data",))
