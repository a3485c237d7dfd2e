"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benchmarks see the real single CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    ``make_mesh`` builds ``Explicit`` axes by default, and
    ``with_sharding_constraint`` — how the cohort step and the SPMD
    layers place their leaves — accepts only specs over ``Auto`` axes.
    Every mesh in this repo comes from here."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         **kw)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (2 pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def largest_divisor(n: int, k: int) -> int:
    """Largest divisor of ``n`` that is <= ``k`` (always >= 1)."""
    k = max(1, min(k, n))
    while n % k:
        k -= 1
    return k


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (examples / integration tests).

    Requested axis sizes are clamped to DIVISORS of the device count
    (largest divisor <= the request), never just ``min``-clamped: e.g.
    ``data=4`` on 6 devices used to build a ``(4, 1)`` mesh — invalid on
    jax versions that require the product to cover the device list, and
    silently stranding two devices on versions that truncate — and
    ``data=0`` divided by zero.  Now ``data=4`` on 6 devices gives
    ``(3, ...)`` and the model axis is clamped to a divisor of what
    remains, so ``data * model`` always divides the device count.
    """
    n = len(jax.devices())
    data = largest_divisor(n, data)
    model = largest_divisor(n // data, model)
    return auto_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[: data * model])


def data_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"


def num_client_groups(mesh) -> int:
    """The FL client-group axis is pod x data."""
    import numpy as np
    return int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
