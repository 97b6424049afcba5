"""Fused Krylov-iteration kernels: one-pass SpMV+reduce and axpy-pair+precond.

The per-iteration cost of the repartitioned pressure CG is pure HBM traffic:
each of the seed's 6-8 separate XLA ops (SpMV, Jacobi divide, three
``HIGHEST``-precision vdots, axpys) re-streams full vectors through HBM.
Following the fused-solver literature the paper builds on (Oliani et al.
arXiv:2403.07882, Tomczak et al. arXiv:1207.1571), this package collapses a
CG iteration into **two** grid passes plus one trivial axpy:

* :func:`spmv_dot_single` — ``Ap = A p`` from the DIA bands **and** the
  block-partial reductions of ``p . Ap`` in the same pass: the bands and
  ``p_pad`` are read from HBM exactly once; each grid step writes its
  ``Ap`` row block and one partial-sum slot (finalized by a tiny
  ``jnp.sum`` over the ``n_blocks`` partials outside the kernel).
* :func:`fused_axpy_precond_single` — the axpy pair ``x += alpha p``,
  ``r -= alpha Ap``, the Jacobi inverse ``z = r * inv_diag``, and the
  block-partials of ``r . z`` and ``r . r`` in one pass — five vector reads
  and three writes instead of the reference's four separate kernels.

The remaining per-iteration work, ``p = z + beta p``, is a single XLA
fusion already and stays in jnp (``repro.solvers.ops``).

Both kernels run on the lane-dense ``(rows/128, 128)`` layout of
``spmv_dia`` (see that module for the band windows and the VMEM budget).
The block partials are ``(8, 128)`` tiles of sublane-group sums, one tile
per grid step, finalized by a ``jnp.sum`` outside the kernel; the axpy
scalar ``alpha`` rides in SMEM.  The ``*_lanes`` cores take one part
already laid out (the stacked wrappers in ``ops`` vmap them); the flat
``*_single`` functions lay out per call.  Zero pad rows contribute
exactly zero to every partial sum, so no masking is needed.  On TPU both refuse 8-byte dtypes
(:func:`~repro.kernels.spmv_dia.spmv_dia.check_kernel_dtypes`).

Each ``pallas_call`` carries an explicit :class:`pl.CostEstimate` built by
:func:`spmv_dot_cost` / :func:`fused_axpy_precond_cost`: the kernel's HBM
contract, which is what ``Compiled.cost_analysis()`` reports for the custom
call on the TPU lowering.  ``benchmarks/fig11_fused_krylov.py`` consumes the
same functions off-TPU, where the interpret-mode lowering un-fuses the grid
into HLO and multiply-counts the VMEM-resident operands (~3x inflation,
measured) and is therefore useless as a byte meter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spmv_dia.spmv_dia import (  # noqa: F401  (re-exported)
    DEFAULT_BLOCK_ROWS, LANES, _ZERO, LaneLayout, band_call,
    check_kernel_dtypes, lane_layout, pick_block_rows, tile_sum)


def _partial_bytes(m: int, block_rows: int, accum_itemsize: int) -> int:
    """Bytes of the ``(8, 128)`` partial tiles, one per row block."""
    return -(-m // block_rows) * 8 * LANES * accum_itemsize


def spmv_dot_cost(nb: int, m: int, plane: int, itemsize: int = 8,
                  block_rows: int = DEFAULT_BLOCK_ROWS,
                  accum_itemsize: int | None = None) -> dict:
    """HBM contract of :func:`spmv_dot_single` (bytes/flops per call).

    ``itemsize`` follows the *storage* dtype (bands, vectors); the
    partial tiles are written at ``accum_itemsize`` (defaults to the
    storage width — the uniform-dtype case).
    """
    acc = accum_itemsize if accum_itemsize is not None else itemsize
    return {
        # bands once + x_pad once (VMEM-resident across the grid) + Ap out
        # + the partial tiles (accumulation width)
        "bytes_accessed": ((nb * m + (m + 2 * plane) + m) * itemsize
                           + _partial_bytes(m, block_rows, acc)),
        "flops": 2 * nb * m + 2 * m,
        "transcendentals": 0,
    }


def fused_axpy_precond_cost(m: int, itemsize: int = 8,
                            block_rows: int = DEFAULT_BLOCK_ROWS,
                            accum_itemsize: int | None = None) -> dict:
    """HBM contract of :func:`fused_axpy_precond_single`."""
    acc = accum_itemsize if accum_itemsize is not None else itemsize
    return {
        # reads x, r, p, Ap, inv_diag; writes x', r', z, 2 * partials
        "bytes_accessed": ((5 * m + 3 * m) * itemsize
                           + 2 * _partial_bytes(m, block_rows, acc)),
        "flops": 9 * m,
        "transcendentals": 0,
    }


def _cost(d: dict) -> pl.CostEstimate:
    return pl.CostEstimate(**d)


# ---------------------------------------------------------------------------
# kernel 1: SpMV + p.Ap block partials (the shared spmv_dia band kernel)
# ---------------------------------------------------------------------------

def spmv_dot_lanes(bands: jax.Array, x_pad: jax.Array, *,
                   offsets: tuple[int, ...], layout: LaneLayout,
                   accum_dtype: str, difference: bool, interpret: bool
                   ) -> tuple[jax.Array, jax.Array]:
    """``(A p, p . A p)`` for one part already in ``layout``: bands
    ``(nb, m_pad/128, 128)``, x_pad ``(x_len/128, 128)``."""
    nb = bands.shape[0]
    check_kernel_dtypes(interpret, bands.dtype, accum_dtype)
    y, partials = band_call(
        bands, x_pad, offsets=offsets, lead=layout.lead,
        block_rows=layout.block_rows, accum_dtype=accum_dtype,
        with_dot=True, difference=difference, interpret=interpret,
        cost_estimate=_cost(spmv_dot_cost(
            nb, layout.m_pad, layout.plane, bands.dtype.itemsize,
            block_rows=layout.block_rows,
            accum_itemsize=jnp.dtype(accum_dtype).itemsize)))
    return y, jnp.sum(partials)


@functools.partial(jax.jit, static_argnames=("offsets", "plane",
                                             "block_rows", "interpret",
                                             "accum_dtype", "difference"))
def spmv_dot_single(bands: jax.Array, x_pad: jax.Array, *,
                    offsets: tuple[int, ...], plane: int,
                    block_rows: int = DEFAULT_BLOCK_ROWS,
                    interpret: bool = False,
                    accum_dtype: str | None = None,
                    difference: bool = False
                    ) -> tuple[jax.Array, jax.Array]:
    """``(A p, p . A p)`` for one part in one grid pass, flat in and out.

    bands: (nb, m); x_pad: (m + 2*plane,) (a longer zero tail is
    ignored).  Ragged ``m`` is padded with zeros (zero bands => zero tail
    contributions to both outputs).  ``accum_dtype`` (a dtype *name*,
    hashable for jit) sets the partial accumulation width; ``None``
    accumulates in the storage dtype.  ``Ap`` comes back in the storage
    dtype, the ``p . A p`` scalar in the accum dtype.  ``difference``:
    the bands are in :func:`~repro.sparse.distributed.difference_form`.
    """
    m = bands.shape[1]
    lay = lane_layout(m, plane, block_rows, bands.dtype)
    y, dot = spmv_dot_lanes(lay.pack(bands), lay.pack_x_pad(x_pad),
                            offsets=offsets, layout=lay,
                            accum_dtype=accum_dtype or bands.dtype.name,
                            difference=difference, interpret=interpret)
    return lay.unpack(y), dot


# ---------------------------------------------------------------------------
# kernel 2: axpy pair + Jacobi inverse + (r.z, r.r) block partials
# ---------------------------------------------------------------------------

def _axpy_precond_kernel(alpha_ref, x_ref, r_ref, p_ref, ap_ref, inv_ref,
                         xo_ref, ro_ref, zo_ref, rz_ref, rr_ref, *,
                         accum_dtype: str):
    a = alpha_ref[0].astype(x_ref.dtype)
    xn = x_ref[...] + a * p_ref[...]
    rn = r_ref[...] - a * ap_ref[...]
    z = rn * inv_ref[...]
    xo_ref[...] = xn
    ro_ref[...] = rn
    zo_ref[...] = z
    # the block reductions upcast per element (no-op when storage == accum)
    rn_a = rn.astype(accum_dtype)
    rz_ref[...] = tile_sum(rn_a * z.astype(accum_dtype))
    rr_ref[...] = tile_sum(rn_a * rn_a)


def axpy_precond_lanes(x: jax.Array, r: jax.Array, p: jax.Array,
                       Ap: jax.Array, inv_diag: jax.Array, alpha: jax.Array,
                       *, block_rows: int, accum_dtype: str,
                       interpret: bool):
    """``(x', r', z, r'.z, r'.r')`` for one part already laid out:
    vectors ``(rows, 128)`` with ``rows * 128`` a multiple of
    ``block_rows``."""
    check_kernel_dtypes(interpret, x.dtype, accum_dtype)
    rows = x.shape[0]
    n_blocks = rows * LANES // block_rows
    blk = pl.BlockSpec((block_rows // LANES, LANES), lambda i: (i, _ZERO))
    part = pl.BlockSpec((8, LANES), lambda i: (i, _ZERO))
    xn, rn, z, rz, rr = pl.pallas_call(
        functools.partial(_axpy_precond_kernel, accum_dtype=accum_dtype),
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((1,), lambda i: (_ZERO,),
                               memory_space=pltpu.SMEM)] + [blk] * 5,
        out_specs=[blk, blk, blk, part, part],
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), x.dtype)] * 3 + [
            jax.ShapeDtypeStruct((n_blocks * 8, LANES), accum_dtype)] * 2,
        cost_estimate=_cost(fused_axpy_precond_cost(
            rows * LANES, x.dtype.itemsize, block_rows=block_rows,
            accum_itemsize=jnp.dtype(accum_dtype).itemsize)),
        interpret=interpret,
        name="axpy_precond",
    )(jnp.reshape(alpha, (1,)).astype(accum_dtype), x, r, p, Ap, inv_diag)
    return xn, rn, z, jnp.sum(rz), jnp.sum(rr)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret",
                                             "accum_dtype"))
def fused_axpy_precond_single(x: jax.Array, r: jax.Array, p: jax.Array,
                              Ap: jax.Array, inv_diag: jax.Array,
                              alpha: jax.Array, *,
                              block_rows: int = DEFAULT_BLOCK_ROWS,
                              interpret: bool = False,
                              accum_dtype: str | None = None):
    """``(x', r', z, r'.z, r'.r')`` for one part in one grid pass, flat.

    ``x' = x + alpha p``, ``r' = r - alpha Ap``, ``z = r' * inv_diag``.
    All inputs (m,); ``alpha`` a scalar, held in SMEM at the accum dtype
    and cast to the storage dtype in the kernel.  Ragged ``m`` padded with
    zeros (zero tails contribute zero to both partials).  Vector outputs
    stay in the storage dtype; the two partial tiles accumulate and
    return in ``accum_dtype`` (``None``: the storage dtype).
    """
    (m,) = x.shape
    lay = lane_layout(m, 0, block_rows, x.dtype)
    xn, rn, z, rz, rr = axpy_precond_lanes(
        *(lay.pack(v) for v in (x, r, p, Ap, inv_diag)), alpha,
        block_rows=lay.block_rows, accum_dtype=accum_dtype or x.dtype.name,
        interpret=interpret)
    return lay.unpack(xn), lay.unpack(rn), lay.unpack(z), rz, rr
