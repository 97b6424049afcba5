"""Per-part row gathers and scatter-adds as flat 1-D index ops.

Stacked arrays carry a small leading part axis ``P``.  Indexing their row
axis directly (``x[:, idx]``, ``x.at[:, idx].add(v)``) lowers on TPU to a
gather/scatter whose operand and result layouts put the part axis minor,
where the ``(8, 128)`` tiling pads it to 128 lanes: a 10-part index op then
stores ~13x its data, and ~17x for ``(P, n, 3)`` fields — at the 210³
cavity more than the chip's memory.  Folding the part axis into the index
(one 1-D gather or scatter over ``P * m`` rows) keeps every layout
lane-dense.  The results are identical: the same rows, visited in the same
order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["take_rows", "add_rows"]


def _flat(idx: jax.Array, P: int, m: int) -> jax.Array:
    """``(P * n,)`` flat row ids of ``idx`` (n,) in every part."""
    idx = jnp.asarray(idx, jnp.int32)
    return (jnp.arange(P, dtype=jnp.int32)[:, None] * m
            + idx[None, :]).reshape(-1)


def take_rows(x: jax.Array, idx: jax.Array) -> jax.Array:
    """``x[:, idx]`` for ``x`` of shape ``(P, m)`` or ``(P, m, k)``."""
    if x.ndim == 3:
        return jnp.stack([take_rows(x[..., c], idx)
                          for c in range(x.shape[2])], axis=-1)
    P, m = x.shape
    return x.reshape(-1)[_flat(idx, P, m)].reshape(P, -1)


def add_rows(x: jax.Array, idx: jax.Array, v: jax.Array) -> jax.Array:
    """``x.at[:, idx].add(v)`` for ``x`` of shape ``(P, m)`` or
    ``(P, m, k)``; ``v`` broadcasts to ``(P, len(idx)[, k])``."""
    n = idx.shape[0]
    if x.ndim == 3:
        v = jnp.broadcast_to(v, (x.shape[0], n, x.shape[2]))
        return jnp.stack([add_rows(x[..., c], idx, v[..., c])
                          for c in range(x.shape[2])], axis=-1)
    P, m = x.shape
    v = jnp.broadcast_to(v, (P, n)).astype(x.dtype)
    return x.reshape(-1).at[_flat(idx, P, m)].add(
        v.reshape(-1)).reshape(P, m)
