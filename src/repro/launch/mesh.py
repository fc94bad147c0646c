"""Production mesh builders.

FUNCTIONS (not module-level constants) so importing never touches jax
device state. Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod: 2x16x16 = 512 chips, axes (pod, data, model) -- the pod axis
joins data parallelism (hierarchical gradient reduction crosses the
inter-pod links).

Every mesh here has `Auto` axes: the model code places activations with
`with_sharding_constraint` hints and lets the partitioner propagate the
rest, which `jax.make_mesh`'s default `Explicit` axes refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """`jax.make_mesh` with every axis `AxisType.Auto`."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_local_mesh(model: int = 1):
    """Whatever this host has (tests / examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return auto_mesh((n // model, model), ("data", "model"))
