"""Pure-jnp oracles for the fused Krylov-iteration kernels.

Identical signatures and semantics to ``krylov_fused.py``; the dot products
are exact-order block-free reductions (``jnp.vdot`` at ``HIGHEST``
precision), which the kernels' block-partial sums must match to f64
round-off — enforced by ``tests/test_krylov_fused.py``.

Per-dtype contract: ``accum_dtype`` mirrors the kernels' accumulation
width — band products and dot partials upcast per element, the vector
outputs come back in the storage dtype, the scalars in the accum dtype.
``None`` keeps everything in the storage dtype (the pre-policy uniform
case, bit-compatible with the seed oracle).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _vdot(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.vdot(a, b, precision=jax.lax.Precision.HIGHEST)


def spmv_dot_ref(bands: jax.Array, x_pad: jax.Array, *,
                 offsets: tuple[int, ...], plane: int,
                 accum_dtype: str | None = None, difference: bool = False):
    """``(A p, p . A p)`` for one part (``difference``: the bands are in
    ``repro.sparse.distributed.difference_form``)."""
    nb, m = bands.shape
    acc_dt = accum_dtype or bands.dtype.name
    p = jax.lax.dynamic_slice_in_dim(x_pad, plane, m).astype(acc_dt)
    y = jnp.zeros((m,), acc_dt)
    for d, off in enumerate(offsets):
        xw = jax.lax.dynamic_slice_in_dim(x_pad, plane + off, m)
        xw = xw.astype(acc_dt)
        y = y + bands[d].astype(acc_dt) * (xw - p if difference and off
                                           else xw)
    # the dot consumes the accum-width Ap (as the kernel does, before the
    # storage-dtype truncation of the vector output)
    return y.astype(bands.dtype), _vdot(p, y)


def fused_axpy_precond_ref(x: jax.Array, r: jax.Array, p: jax.Array,
                           Ap: jax.Array, inv_diag: jax.Array,
                           alpha: jax.Array,
                           accum_dtype: str | None = None):
    """``(x', r', z, r'.z, r'.r')`` for one part."""
    acc_dt = accum_dtype or x.dtype.name
    a = alpha.astype(x.dtype)
    xn = x + a * p
    rn = r - a * Ap
    z = rn * inv_diag
    rn_a = rn.astype(acc_dt)
    return xn, rn, z, _vdot(rn_a, z.astype(acc_dt)), _vdot(rn_a, rn_a)
