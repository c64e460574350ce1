"""Production mesh builders.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run must set XLA_FLAGS
before any jax initialization.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: the logical-axis constraints of
    :mod:`repro.dist.sharding` (``with_sharding_constraint``) may only name
    Auto axes, and ``make_mesh`` defaults to Explicit ones."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e pod mesh: 16x16 = 256 chips/pod; 2 pods = 512 chips.

    Axes: "data" (+"pod" across pods) carry DP; "model" carries TP/EP/SP.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over the real local devices (tests / CPU drivers)."""
    devices = jax.devices()
    data = min(data, len(devices))
    model = min(model, max(1, len(devices) // data))
    return _mesh((data, model), ("data", "model"),
                 devices=devices[:data * model])


def make_serving_mesh(n_shards: int = 1):
    """1-D serving mesh: ``n_shards`` ways of data parallelism.

    The sharded :class:`~repro.engine.engine.Engine` splits the batch axis
    (logical "batch" -> physical "data") across this mesh, built on the
    first ``n_shards`` local devices.  Fewer devices than shards raises: a
    ``mesh:<profile>:4`` engine never quietly runs replicated on one chip.
    (The CPU test harness provides virtual devices for this, see
    ``tests/conftest.py``.)
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    devices = jax.devices()
    if n_shards > len(devices):
        raise ValueError(
            f"a {n_shards}-shard serving mesh needs {n_shards} devices; "
            f"this host has {len(devices)} ({devices[0].platform})")
    return _mesh((int(n_shards),), ("data",), devices=devices[:n_shards])
