"""Production mesh builders. Functions, not module constants, so importing
never touches jax device state (the dry-run must set XLA_FLAGS first)."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def auto_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with Auto axes. The sharding rules
    (dist/sharding.py) lay arrays out with NamedSharding and
    with_sharding_constraint and let GSPMD propagate the rest; JAX's
    default Explicit axes would instead type every array with its
    sharding."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def feasible_mesh_shape(n: int, data: int, model: int) -> Tuple[int, int]:
    """Largest (data, model) grid that fits on ``n`` devices.

    When the request fits, it is returned unchanged. When it oversubscribes,
    the model axis is preserved as far as possible — clamped to the largest
    divisor of ``n`` not exceeding the request — and data fills the rest,
    instead of silently dropping model parallelism altogether.
    """
    if data * model <= n:
        return data, model
    model = max(m for m in range(1, min(model, n) + 1) if n % m == 0)
    return n // model, model


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data, model = feasible_mesh_shape(n, data, model)
    return auto_mesh((data, model), ("data", "model"))
