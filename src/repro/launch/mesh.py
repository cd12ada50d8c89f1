"""Mesh construction: every mesh of the repository, tests included, is
built by ``make_mesh`` here.

Functions, not module-level constants: importing this module never
touches jax device state (device count locks on first jax init)."""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with Auto axis types. jax defaults to Explicit
    axes, under which every op must name how its operands are sharded;
    the models instead annotate activations through
    ``distributed.sharding.constrain`` and leave the rest to the
    partitioner, which is what Auto axes do."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256-chip single pod (data, model), or 2 pods = 512 chips
    with a leading 'pod' axis. data+pod are the DP/FSDP axes; 'model' is
    tensor/expert parallel (DESIGN.md section 4)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1, devices=None):
    """(data, model) mesh over ``devices`` (default: every device there
    is) -- used by train.py/serve.py for actually-running jobs."""
    devices = jax.devices() if devices is None else list(devices)
    n = len(devices)
    assert n % model_parallel == 0
    return make_mesh((n // model_parallel, model_parallel),
                     ("data", "model"), devices=devices)
