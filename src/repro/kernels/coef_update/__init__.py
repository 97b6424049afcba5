"""Permutation-apply kernel: solver values = buf[P∘U] (paper fig. 3a/3b).

The runtime half of the repartitioning split: the plan is symbolic and built
once (:mod:`repro.core.repartition`); every outer iteration only coefficient
*values* move.  On GPU the paper scatters into a row-major COO view; on TPU
the same permutation is a blocked **gather** with the staging buffer resident
in VMEM.

Layout & padding contract (``coef_update.py``):

* ``buf``: ``(alpha * L + 1,)`` concatenated fine-part coefficient buffers
  per coarse part, ``+1`` for the sentinel zero slot that empty ELL/DIA
  positions gather from (``ops.py`` asserts the VMEM budget);
* ``src``: flattened plan indices (``ell_src``/``dia_src``), compile-time
  constants streamed in blocks of ``block`` (default 4096; callers pad the
  index array with the sentinel so ``n_out % block == 0`` and slice off the
  padding after);
* the gather lowers via the vector permute unit; on very old toolchains it
  falls back to a scalar loop — still correct.

Entry point: :func:`~repro.kernels.coef_update.ops.coef_update_pallas`
(stacked coarse parts, interpret-mode fallback off-TPU).  ``ref.py`` is the
jnp oracle (``buf[src]``); bit-exact agreement per dtype is enforced by
``tests/test_kernels.py`` (docs/kernels.md).  The jit-level analogue used
inside the PISO step — with compiled-program reuse across equal-shape
plans — is :class:`repro.core.update.UpdaterPool`.
"""
from repro.kernels.coef_update.ops import coef_update_pallas  # noqa: F401
