"""Public stacked-part wrappers for the fused Krylov-iteration kernels.

Mirrors the ``spmv_dia`` wrapper conventions: stacked ``(P, ...)`` arrays
in the kernels' lane-dense layout (``LaneLayout.pack``), the halo'd
``x_pad`` built from them (its static part-axis shifts lower to
collective-permute under pjit), vmap over parts, interpret-mode fallback
off-TPU.  The per-part block partials are finalized into **global**
scalars with a final ``jnp.sum`` over parts, which lowers to the same
all-reduce a reference dot emits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.krylov_fused.krylov_fused import (LaneLayout,
                                                     axpy_precond_lanes,
                                                     spmv_dot_lanes)
from repro.kernels.spmv_dia.ops import _on_tpu, lane_x_pad


@functools.partial(jax.jit, static_argnames=("offsets", "layout",
                                             "accum_dtype", "difference"))
def fused_matvec_dot(bands: jax.Array, x: jax.Array, *,
                     offsets: tuple[int, ...], layout: LaneLayout,
                     accum_dtype: str | None = None,
                     difference: bool = False
                     ) -> tuple[jax.Array, jax.Array]:
    """``(A x, x . A x)`` over stacked parts in the kernel layout: bands
    (P, nb, m_pad/128, 128), x (P, m_pad/128, 128).

    One HBM pass over the bands and the halo'd vector per call.
    ``accum_dtype`` (dtype name) sets the partial-reduction width for
    low-precision storage policies; ``None`` keeps the storage dtype.
    ``difference``: as in :func:`~repro.kernels.spmv_dia.ops.spmv_dia_pallas`.
    """
    fn = functools.partial(spmv_dot_lanes, offsets=offsets, layout=layout,
                           accum_dtype=accum_dtype or bands.dtype.name,
                           difference=difference, interpret=not _on_tpu())
    y, part = jax.vmap(fn)(bands, lane_x_pad(x, layout))
    return y, jnp.sum(part)


@functools.partial(jax.jit, static_argnames=("block_rows", "accum_dtype"))
def fused_update_step(x: jax.Array, r: jax.Array, p: jax.Array,
                      Ap: jax.Array, inv_diag: jax.Array, alpha: jax.Array,
                      *, block_rows: int, accum_dtype: str | None = None):
    """Fused axpy pair + Jacobi inverse + global ``(r'.z, r'.r)`` dots.

    All vectors stacked in the kernel layout (P, m_pad/128, 128), with
    ``m_pad`` a multiple of ``block_rows``; ``alpha`` a global scalar.
    Returns ``(x', r', z, rz, rr)`` with the dots reduced over all parts
    (the two scalars in ``accum_dtype`` when given, else the storage
    dtype).
    """
    fn = functools.partial(axpy_precond_lanes, block_rows=block_rows,
                           accum_dtype=accum_dtype or x.dtype.name,
                           interpret=not _on_tpu())
    xn, rn, z, rz, rr = jax.vmap(fn, in_axes=(0, 0, 0, 0, 0, None))(
        x, r, p, Ap, inv_diag, alpha)
    return xn, rn, z, jnp.sum(rz), jnp.sum(rr)
