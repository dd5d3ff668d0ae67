"""Production mesh construction.

A function (never a module-level constant) so importing this module never
touches jax device state. Single pod: 16×16 = 256 chips ("data","model").
Multi-pod: 2×16×16 = 512 chips ("pod","data","model") — the "pod" axis is
the slow inter-pod (DCN-ish) dimension; the sharding rules fold it into
the batch axis (DESIGN.md §4).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto``: the compiler propagates
    shardings from the rule tables' constraints, as this repo's layers
    expect. ``make_mesh``'s default, ``Explicit`` axes, instead type every
    array by its sharding and refuses ops such as the embedding gather on
    a model-sharded table."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Small mesh over however many (real or forced) devices exist —
    used by tests and the CPU examples."""
    n = jax.device_count()
    data = n // model_axis
    return _mesh((data, model_axis), ("data", "model"))


def make_domain_mesh(shape: tuple[int, ...]):
    """1-D/2-D mesh for sharded windowed-domain execution.

    Axis names follow the sharding rule tables ("rows" → ``data``,
    "cols" → ``model``), so ``halo_exchange.default_domain_spec``
    resolves without explicit in_specs. ``shape=(A,)`` shards rows only;
    ``shape=(A, B)`` shards rows over A devices and lanes over B.
    """
    if not 1 <= len(shape) <= 2:
        raise ValueError(f"domain meshes are 1-D or 2-D, got {shape}")
    names = ("data", "model")[: len(shape)]
    return _mesh(tuple(shape), names)
