"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
initialization, and smoke tests/benches must keep seeing 1 device.

Topology contract (DESIGN.md §4):
    single pod : (16, 16)    axes ("data", "model")      — 256 chips, ICI
    multi-pod  : (2, 16, 16) axes ("pod", "data", "model") — 512 chips,
                 pods linked by DCN; only gradient all-reduce crosses pods.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``.  jax defaults to
    ``Explicit`` axes, under which the models' embedding gathers need an
    ``out_sharding``; the sharding rules here are written for ``Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2):
    """Small mesh for CPU multi-device tests (subprocess sets device count)."""
    return make_mesh((data, model), ("data", "model"))
