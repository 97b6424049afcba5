"""Banded (DIA) SpMV Pallas kernel — the repartitioned solver's hot loop.

TPU adaptation of the paper's GPU row-major COO SpMV: the fused FVM matrix is
7-banded (``RepartitionPlan.dia_offsets = [-plane, -nx, -1, 0, +1, +nx,
+plane]``), so ``y = A x`` is seven shifted fused multiply-adds over
``x_pad = [down-halo | x | up-halo]`` — no gather, no atomics, pure VPU work.

Layout & tiling contract (``spmv_dia.py``):

* every part vector is lane-dense ``(rows / 128, 128)``; bands
  ``(n_bands, rows / 128, 128)`` per part, walked in ``block_rows`` row
  blocks of whole ``(sublanes, 128)`` tiles (``pick_block_rows``); the
  rows are zero-padded to whole blocks (``LaneLayout``, from
  ``lane_layout``).
* ``x_pad``: the part's row 0 at a tile-aligned ``lead``; resident in
  VMEM for the whole grid, with the scoped VMEM sized by
  ``vmem_limit_bytes``; band windows are aligned dynamic loads plus
  static sublane slices and lane rolls.
* halo planes are zero at physical boundaries, matching the zero interface
  coefficients there, so no masking is needed.
* ``difference=True``: the offset-0 band holds the row sums and the other
  bands act on ``x_j - x_i`` (the low-precision solves' form).
* compiled (non-interpret) calls refuse 8-byte dtypes
  (``check_kernel_dtypes``).

Entry points: :func:`~repro.kernels.spmv_dia.ops.spmv_dia_pallas` (stacked
parts already laid out, ``(P, nb, rows/128, 128)``; interpret mode
off-TPU) and ``spmv_dia_single`` (one flat part, laid out per call).
``ref.py`` holds the pure-jnp oracle ``spmv_dia_ref``, enforced by
``tests/test_krylov_fused.py`` (see docs/kernels.md).
"""
from repro.kernels.spmv_dia.ops import spmv_dia_pallas  # noqa: F401
