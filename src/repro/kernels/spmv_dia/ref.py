"""Pure-jnp oracle for the DIA SpMV kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def spmv_dia_ref(bands: jax.Array, x_pad: jax.Array, *,
                 offsets: tuple[int, ...], plane: int,
                 difference: bool = False) -> jax.Array:
    nb, m = bands.shape
    x = jax.lax.dynamic_slice_in_dim(x_pad, plane, m)
    y = jnp.zeros((m,), bands.dtype)
    for d, off in enumerate(offsets):
        xd = jax.lax.dynamic_slice_in_dim(x_pad, plane + off, m)
        y = y + bands[d] * (xd - x if difference and off else xd)
    return y
