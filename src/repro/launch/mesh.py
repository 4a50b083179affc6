"""Host mesh construction.

Defined as a FUNCTION (not a module constant) so importing never touches
jax device state.
"""

from __future__ import annotations

import jax


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over however many (host/forced) devices exist — used by
    tests and the CPU examples."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return jax.make_mesh(
        (data, model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
