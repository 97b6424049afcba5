"""Public wrapper: stacked-part banded SpMV through the Pallas kernel.

Falls back to interpret mode off-TPU (this container) — same kernel body,
executed in Python; numerics identical to the TPU lowering path.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.spmv_dia.spmv_dia import (LaneLayout, band_call,
                                             check_kernel_dtypes)
from repro.sparse.distributed import x_pad_lanes


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def lane_x_pad(x: jax.Array, layout: LaneLayout) -> jax.Array:
    """The halo'd ``x_pad`` of lane-dense stacked parts (the shifts across
    the part axis lower to collective-permute under pjit)."""
    return x_pad_lanes(x, m=layout.m, plane=layout.plane, lead=layout.lead,
                       x_len=layout.x_len)


@functools.partial(jax.jit, static_argnames=("offsets", "layout",
                                             "accum_dtype", "difference"))
def spmv_dia_pallas(bands: jax.Array, x: jax.Array, *,
                    offsets: tuple[int, ...], layout: LaneLayout,
                    accum_dtype: str | None = None,
                    difference: bool = False) -> jax.Array:
    """Stacked SpMV in the kernel layout: bands (P, nb, m_pad/128, 128),
    x (P, m_pad/128, 128) → y (P, m_pad/128, 128) (``layout.pack``).

    Builds the halo'd x_pad, then vmaps the band kernel over parts.
    ``accum_dtype`` (dtype name) widens the in-kernel row accumulator for
    low-precision bands; ``None`` accumulates in the storage dtype.
    ``difference``: the bands are in
    :func:`~repro.sparse.distributed.difference_form`.
    """
    accum_dtype = accum_dtype or bands.dtype.name
    interpret = not _on_tpu()
    check_kernel_dtypes(interpret, bands.dtype, accum_dtype)
    fn = functools.partial(band_call, offsets=offsets, lead=layout.lead,
                           block_rows=layout.block_rows,
                           accum_dtype=accum_dtype, with_dot=False,
                           difference=difference, interpret=interpret)
    (y,) = jax.vmap(fn)(bands, lane_x_pad(x, layout))
    return y
