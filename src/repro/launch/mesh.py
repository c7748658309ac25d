"""Mesh construction.

FUNCTIONS, not module-level constants — importing this module never
touches jax device state (required for the smoke tests, which must see
one CPU device, vs the dry-run, which forces 512 host devices *before*
jax init).

Every mesh in the repo is built by :func:`make_mesh`: all axes are
``Auto``, so tensor parallelism over 'model' is left to XLA's SPMD
partitioner and ``with_sharding_constraint`` may name any axis (jax's
``make_mesh`` defaults to ``Explicit`` axes, which reject both).
"""

from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(shape: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(
        tuple(shape), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def parse_mesh(spec: str):
    """The launcher's ``--mesh`` string → an Auto mesh: 'DxM' over
    ('data', 'model'), 'PxDxM' over ('pod', 'data', 'model')."""
    dims = [int(x) for x in spec.split("x")]
    names = ("pod", "data", "model")[-len(dims):]
    return make_mesh(dims, names)


def make_production_mesh(*, multi_pod: bool = False):
    """v5e production mesh: 16x16 = 256 chips per pod; 2 pods = 512."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def worker_count(mesh) -> int:
    out = 1
    for a in data_axes(mesh):
        out *= mesh.shape[a]
    return out
