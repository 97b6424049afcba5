"""Fused on-device FVM momentum assembly — the "full refactoring" baseline.

The paper contrasts plugin-style acceleration (CPU assembly + repartitioned
GPU solve — this repo's main path) with refactoring assembly onto the
accelerator.  This package is the TPU rendering of the latter: one fused
Pallas pass turns cell-indexed face fluxes/conductances directly into the 7
DIA bands (upwind convection, central diffusion, diagonal closure) — no LDU
detour, no update pattern, no host traffic.

Layout & tiling contract (``stencil_assembly.py``):

* inputs are **cell-indexed** face arrays per part, each padded by ``plane``
  on both ends (``ops.py`` builds them: interpolation + masking + part-halo
  exchange); ``phi_x[c]`` is the flux through the face between ``c`` and
  ``c+1`` (zero where absent), strides ``1/nx/plane`` for x/y/z;
* the grid walks row blocks of ``block_rows`` (default 2048, must divide the
  per-part cell count ``m``; ``ops.py`` pads to a multiple); every input is
  fully VMEM-resident per step and neighbour values come from static
  ``±1/±nx/±plane`` shifted windows — VPU-friendly, gather-free;
* output band order matches ``RepartitionPlan.dia_offsets``:
  ``[-plane, -nx, -1, 0, +1, +nx, +plane]``.

Entry points: :func:`~repro.kernels.stencil_assembly.ops.momentum_bands_pallas`
(stacked parts, interpret-mode fallback off-TPU) and
``momentum_bands_single``.  ``ref.py`` is the jnp oracle; the contract is
bit-exact agreement per dtype (``tests/test_kernels.py``; docs/kernels.md).
"""
from repro.kernels.stencil_assembly.ops import momentum_bands_pallas  # noqa: F401
