"""Banded (DIA) SpMV Pallas kernel — the repartitioned solver's hot loop.

TPU adaptation of the paper's GPU COO SpMV: a structured-FVM matrix is a
7-band matrix, so ``y = A x`` becomes seven shifted fused multiply-adds over
``x_pad = [down-halo | x | up-halo]`` — no gather, no atomics; pure VPU
(8x128) work streaming the bands from HBM through VMEM.

**Lane-dense layout.**  Mosaic loads a rank-1 VMEM ref only at offsets
that are multiples of 1024, and a band shift (``lead + off``) is an
arbitrary static row offset.  So every part vector is held as
``(rows / 128, 128)``: row ``i`` sits at ``[i // 128, i % 128]``
(:class:`LaneLayout`; the solver packs its vectors into it once per
sweep, and ``x`` sits tile-aligned inside ``x_pad``, so building
``x_pad`` moves no lanes).  A band window for a row block starting at
sublane row ``r0`` is

* one dynamic load of ``R/128 + sub`` sublane rows starting at the
  ``sub``-aligned row ``r0 + base`` (``sub`` = the dtype's sublane packing:
  8 rows for 4-byte, 16 for 2-byte storage),
* a static sublane slice for the rest of the whole-row part of the shift,
* and, for the sub-row remainder ``rem``, two adjacent row slices each
  rotated by ``128 - rem`` lanes (``pltpu.roll``) and joined by a lane-iota
  select.

Windows are upcast to the accumulation dtype on load, so the slices and
rotations always act on 32-bit values.

**Difference form.**  With ``difference=True`` the offset-0 band holds the
row sums and the other bands act on ``x_j - x_i``
(:func:`repro.sparse.distributed.difference_form`): the same operator,
but one that maps constants to the row sums exactly however its values
were rounded.  The low-precision solves use it (see ``solvers/cg.py``).

**Grid and VMEM.**  The grid walks row blocks of ``block_rows`` (a
multiple of the ``sub x 128`` tile).  Per step the kernel sees a
``(nb, R/128, 128)`` tile of the bands and the whole ``x_pad`` in VMEM;
:func:`vmem_limit_bytes` sizes the scoped VMEM for the double-buffered
``x_pad`` plus the band tiles and raises when a part cannot fit.  The
block partials of the optional ``p . Ap`` reduction are written as one
``(8, 128)`` tile per grid step (sublane-group sums; the final scalar is a
``jnp.sum`` outside the kernel).

**Dtypes.**  The TPU lowering takes 4- and 2-byte storage only: an 8-byte
dtype raises in :func:`check_kernel_dtypes` before any ``pallas_call`` is
built (an f64 custom call is unimplemented on TPU).  The f64 solver path
runs the jnp operators instead.  Interpret mode (off-TPU tests) still
accepts f64.  Every index map and roll shift is an explicit ``int32`` so
that ``jax_enable_x64`` leaks no i64 into the kernel.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_BLOCK_ROWS = 32768
# scoped-VMEM ceiling for one kernel (a v5e core has 128 MiB of VMEM)
VMEM_CAP_BYTES = 96 * 2**20
# index-map constants: int32 even under jax_enable_x64 (numpy, so that
# importing this module initializes no JAX backend)
_ZERO = np.int32(0)


def _round_up(n: int, k: int) -> int:
    return -(-n // k) * k


def sublanes(dtype) -> int:
    """Rows of one native VMEM tile: 8 for 4-byte, 16 for 2-byte dtypes."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def row_tile(dtype) -> int:
    """Smallest legal row block: one ``(sublanes, 128)`` tile."""
    return sublanes(dtype) * LANES


def pick_block_rows(m: int, dtype=jnp.float32,
                    cap: int = DEFAULT_BLOCK_ROWS) -> int:
    """Row-block size for an ``m``-row part: ``cap`` when the part fills
    it, else ``m`` rounded up to the dtype's row tile, so small parts
    (full-mesh shards, tests) run a single-block grid."""
    return min(_round_up(cap, row_tile(dtype)), _round_up(m, row_tile(dtype)))


@dataclasses.dataclass(frozen=True)
class LaneLayout:
    """Where one part's rows sit in the lane-dense kernel layout.

    A vector of ``m`` rows is held as ``(m_pad / 128, 128)`` (zero pad
    rows); ``x_pad`` as ``(x_len / 128, 128)`` with the part's row 0 at
    row ``lead`` — ``plane`` rounded up to a whole tile, so that ``x``
    sits tile-aligned inside ``x_pad`` and the down halo ends right
    before it.  Hashable: the stacked wrappers take it as a static
    argument.
    """

    m: int            # real rows per part
    plane: int        # halo depth: the largest band shift
    block_rows: int   # rows per grid step, whole (sublanes, 128) tiles
    m_pad: int        # m rounded up to whole blocks
    lead: int         # rows of x_pad before the part's row 0
    x_len: int        # rows of x_pad, whole tiles

    def pack(self, v: jax.Array) -> jax.Array:
        """``(..., m)`` -> ``(..., m_pad / 128, 128)``, zero pad rows."""
        return to_lanes(v, self.m_pad)

    def unpack(self, v: jax.Array) -> jax.Array:
        """``(..., m_pad / 128, 128)`` -> ``(..., m)``."""
        return v.reshape(*v.shape[:-2], -1)[..., :self.m]

    def pack_x_pad(self, x_pad: jax.Array) -> jax.Array:
        """A flat ``[down-halo | x | up-halo]`` (at least ``m + 2 plane``
        long) -> ``(x_len / 128, 128)`` with ``x`` at row ``lead``."""
        v = x_pad[..., :self.m + 2 * self.plane]
        pad = [(0, 0)] * (v.ndim - 1)
        v = jnp.pad(v, pad + [(self.lead - self.plane, 0)])
        return to_lanes(v, self.x_len)


def lane_layout(m: int, plane: int, block_rows: int, dtype) -> LaneLayout:
    """The :class:`LaneLayout` of an ``m``-row part at ``dtype``.

    ``block_rows`` is rounded up to the dtype's row tile; ``x_len`` covers
    the band windows of the last block (each window loads ``sub`` rows
    past its block, from a ``sub``-aligned base below the shift).
    """
    tile = row_tile(dtype)
    br = _round_up(block_rows, tile)
    mp = _round_up(m, br)
    lead = _round_up(plane, tile)
    sub = sublanes(dtype)
    base_max = ((lead + plane) // LANES) // sub * sub
    return LaneLayout(m=m, plane=plane, block_rows=br, m_pad=mp, lead=lead,
                      x_len=(mp // LANES + base_max + sub) * LANES)


def check_kernel_dtypes(interpret: bool, *dtypes) -> None:
    """Refuse 8-byte dtypes in a compiled (non-interpret) kernel."""
    if interpret:
        return
    wide = [jnp.dtype(d).name for d in dtypes if jnp.dtype(d).itemsize > 4]
    if wide:
        raise ValueError(
            f"Pallas TPU kernels take 4- or 2-byte dtypes, got {wide}: an "
            f"f64 custom call is unimplemented on TPU. Use a refined "
            f"precision policy (f32_ir / bf16_ir) or the reference backend.")


def vmem_limit_bytes(x_len: int, nb: int, block_rows: int, dtype,
                     accum_dtype) -> int:
    """Scoped VMEM for one band-kernel call (raises when it cannot fit).

    Double-buffered ``x_pad``, band tile and output tile, the partial
    tile, and room for the 32-bit band windows and accumulators.
    """
    item = jnp.dtype(dtype).itemsize
    acc = jnp.dtype(accum_dtype).itemsize
    window = (block_rows + sublanes(dtype) * LANES) * max(acc, 4)
    need = (2 * (x_len + (nb + 1) * block_rows) * item
            + 2 * 8 * LANES * acc + 16 * window + 4 * 2**20)
    if need > VMEM_CAP_BYTES:
        raise ValueError(
            f"x_pad of {x_len} rows does not fit VMEM: the kernel needs "
            f"{need / 2**20:.1f} MiB, the cap is "
            f"{VMEM_CAP_BYTES / 2**20:.0f} MiB")
    return _round_up(need, 2**20)


def to_lanes(v: jax.Array, length: int) -> jax.Array:
    """Zero-pad the last axis to ``length`` and fold it to ``(.., 128)``."""
    pad = length - v.shape[-1]
    if pad:
        v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, pad)])
    return v.reshape(*v.shape[:-1], length // LANES, LANES)


def _band_windows(x_ref, r0, shifts, *, br: int, sub: int, dtype):
    """Lane-dense windows ``x_pad[row0 + s : row0 + s + R]`` per shift."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (br, LANES), 1)
    loads, out = {}, {}
    for s in shifts:
        if s in out:
            continue
        q, rem = divmod(s, LANES)
        base = q // sub * sub
        if base not in loads:
            start = pl.multiple_of(r0 + base, sub)
            loads[base] = x_ref[pl.ds(start, br + sub), :].astype(dtype)
        win, qs = loads[base], q - base
        w = win[qs:qs + br]
        if rem:
            shift = jnp.int32(LANES - rem)
            w = jnp.where(lane < LANES - rem, pltpu.roll(w, shift, 1),
                          pltpu.roll(win[qs + 1:qs + 1 + br], shift, 1))
        out[s] = w
    return out


def tile_sum(v: jax.Array) -> jax.Array:
    """Fold ``(R/128, 128)`` to one ``(8, 128)`` tile of partial sums."""
    return v.reshape(-1, 8, LANES).sum(axis=0)


def _band_kernel(bands_ref, xpad_ref, y_ref, *dot_ref, offsets, lead,
                 br, sub, accum_dtype, difference):
    r0 = pl.program_id(0) * br
    shifts = [lead + off for off in offsets]
    center = bool(dot_ref) or difference
    if center:
        shifts.append(lead)
    win = _band_windows(xpad_ref, r0, shifts, br=br, sub=sub,
                        dtype=accum_dtype)
    acc = jnp.zeros((br, LANES), accum_dtype)
    for d, off in enumerate(offsets):
        w = win[lead + off]
        if difference and off:
            w = w - win[lead]
        acc = acc + bands_ref[d].astype(accum_dtype) * w
    y_ref[...] = acc.astype(y_ref.dtype)
    if dot_ref:
        # the block's rows of p itself (the offset-0 window) feed p . Ap
        dot_ref[0][...] = tile_sum(win[lead] * acc)


def band_call(bands: jax.Array, x_pad: jax.Array, *,
              offsets: tuple[int, ...], lead: int, block_rows: int,
              accum_dtype, with_dot: bool, difference: bool,
              interpret: bool,
              cost_estimate: pl.CostEstimate | None = None):
    """The lane-dense band kernel on one laid-out part.

    bands ``(nb, m_pad/128, 128)``; x_pad ``(x_len/128, 128)`` with the
    part's row 0 at row ``lead`` (:class:`LaneLayout`).  With
    ``difference`` the offset-0 band holds the row sums and every other
    band acts on ``x_j - x_i`` (see :func:`repro.sparse.distributed.
    difference_form`).  Returns ``[y]`` ``(m_pad/128, 128)`` in the
    bands' dtype, plus, with ``with_dot``, the ``(n_blocks * 8, 128)``
    partial tiles of ``p . Ap`` in ``accum_dtype``.
    """
    nb, rows, _ = bands.shape
    sub = sublanes(bands.dtype)
    br = block_rows // LANES
    grid = (rows // br,)
    out_specs = [pl.BlockSpec((br, LANES), lambda i: (i, _ZERO))]
    out_shape = [jax.ShapeDtypeStruct((rows, LANES), bands.dtype)]
    if with_dot:
        out_specs.append(pl.BlockSpec((8, LANES), lambda i: (i, _ZERO)))
        out_shape.append(
            jax.ShapeDtypeStruct((grid[0] * 8, LANES), accum_dtype))
    return pl.pallas_call(
        functools.partial(_band_kernel, offsets=offsets, lead=lead, br=br,
                          sub=sub, accum_dtype=accum_dtype,
                          difference=difference),
        grid=grid,
        in_specs=[
            pl.BlockSpec((nb, br, LANES), lambda i: (_ZERO, i, _ZERO)),
            # the whole x_pad, VMEM-resident across the grid
            pl.BlockSpec(x_pad.shape, lambda i: (_ZERO, _ZERO)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes(
                x_pad.size, nb, block_rows, bands.dtype, accum_dtype)),
        cost_estimate=cost_estimate,
        interpret=interpret,
        name="spmv_dot" if with_dot else "spmv",
    )(bands, x_pad)


@functools.partial(jax.jit,
                   static_argnames=("offsets", "plane", "block_rows",
                                    "interpret", "accum_dtype",
                                    "difference"))
def spmv_dia_single(bands: jax.Array, x_pad: jax.Array, *,
                    offsets: tuple[int, ...], plane: int,
                    block_rows: int = DEFAULT_BLOCK_ROWS,
                    interpret: bool = False,
                    accum_dtype: str | None = None,
                    difference: bool = False) -> jax.Array:
    """y = A @ x for one part, flat in and out.  bands: (nb, m); x_pad:
    (m + 2*plane,) ``[down-halo | x | up-halo]`` (a longer zero tail is
    ignored).

    Lays the operands out per call (:class:`LaneLayout`); the solver's
    hot loop keeps them laid out instead (``ops.spmv_dia_pallas``).
    ``accum_dtype`` (dtype *name*, jit-hashable) widens the row
    accumulator for low-precision bands; ``y`` comes back in the storage
    dtype.  ``None`` accumulates in the storage dtype.  ``difference``:
    the bands are in :func:`~repro.sparse.distributed.difference_form`.
    """
    nb, m = bands.shape
    accum_dtype = accum_dtype or bands.dtype.name
    check_kernel_dtypes(interpret, bands.dtype, accum_dtype)
    lay = lane_layout(m, plane, block_rows, bands.dtype)
    (y,) = band_call(lay.pack(bands), lay.pack_x_pad(x_pad),
                     offsets=offsets, lead=lay.lead,
                     block_rows=lay.block_rows, accum_dtype=accum_dtype,
                     with_dot=False, difference=difference,
                     interpret=interpret)
    return lay.unpack(y)
