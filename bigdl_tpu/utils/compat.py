"""The JAX names the parallel code shares, for the one installation there
is (JAX 0.9): ``shard_map`` and ``axis_size`` are JAX's own, and
``pvary`` spells "mark as varying over these mesh axes" with
``lax.pcast`` (``lax.pvary`` is deprecated in its favour).
"""
from __future__ import annotations

from jax import lax, shard_map  # noqa: F401 — re-exported
from jax.lax import axis_size  # noqa: F401 — re-exported


def pvary(x, axes):
    """Mark a value as varying over named axes (strict-VMA shard_map)."""
    return lax.pcast(x, axes, to="varying")
