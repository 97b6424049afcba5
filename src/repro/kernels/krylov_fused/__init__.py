"""Fused Krylov-iteration core — one-pass SpMV+reduce and axpy-pair kernels.

A CG iteration of the repartitioned pressure solve collapses from the
seed's 6-8 separate XLA ops into two grid passes plus one jnp axpy:

* ``spmv_dot_single`` / ``ops.fused_matvec_dot`` — ``Ap`` from the DIA
  bands **and** the block-partial ``p . Ap`` reduction in a single pass:
  bands and the halo'd vector are read from HBM once per iteration.
* ``fused_axpy_precond_single`` / ``ops.fused_update_step`` — the axpy
  pair ``x += alpha p``, ``r -= alpha Ap``, the Jacobi inverse
  ``z = r * inv_diag`` and the ``r . z`` / ``r . r`` block partials in a
  second pass (five reads, three writes).
* ``p = z + beta p`` stays a plain jnp axpy (already a single fusion).

Layout contract: same as ``spmv_dia`` — lane-dense ``(rows/128, 128)``
vectors and bands walked in ``block_rows`` row blocks, the halo'd
``x_pad`` VMEM-resident across the grid.  The stacked wrappers take
arrays already laid out (``LaneLayout.pack``; the solver packs once per
sweep); the ``*_single`` functions take flat ones.  Zero pad rows
contribute exactly zero to every block partial, so the reductions need
no masking.  Each ``pallas_call`` declares its HBM
contract via ``pl.CostEstimate`` (``spmv_dot_cost`` /
``fused_axpy_precond_cost``) — the numbers ``Compiled.cost_analysis()``
reports for the TPU lowering and the numbers
``benchmarks/fig11_fused_krylov.py`` uses off-TPU, where interpret mode
un-fuses the grid and inflates static byte counts ~3x.

``ref.py`` holds the jnp oracles (``spmv_dot_ref``,
``fused_axpy_precond_ref``); parity to f64 round-off is enforced by
``tests/test_krylov_fused.py``.  The consumer is the ``SolverOps`` fused
backend in ``repro.solvers.ops``.
"""
from repro.kernels.krylov_fused.ops import (  # noqa: F401
    fused_matvec_dot, fused_update_step)
