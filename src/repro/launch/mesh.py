"""Production mesh construction.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax init,
and smoke tests must keep seeing exactly 1 device.

Every mesh here has Auto axes.  `jax.make_mesh` defaults to Explicit axes,
under which the backward of a `shard_map` program (the sharded lookup)
asks for the mesh to be set as the ambient jit context; with Auto axes
the GSPMD partitioner places the cross-axis ops itself.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """`jax.make_mesh` with Auto axis types (see the module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape=None, axes=("data", "model")):
    """Best-effort mesh over whatever devices exist (tests / CPU driver)."""
    n = jax.device_count()
    if shape is None:
        model = 1
        for cand in (4, 2):
            if n % cand == 0 and n >= cand * 2:
                model = cand
                break
        shape = (n // model, model)
    return make_mesh(shape, axes)
