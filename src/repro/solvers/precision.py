"""PrecisionPolicy — mixed-precision Krylov storage with f64 refinement.

Every bytes/iter row in ``BENCH_krylov.json`` says the Krylov core is
bandwidth-bound: the fused kernels already buy ~1.85x bytes/iter at
alpha=4, and the remaining lever is the *width* of every band and vector
the hot loop streams.  A :class:`PrecisionPolicy` names one point on that
trade (the classic GPU-CFD precision trade of Niemeyer & Sung, exploited
by the Ginkgo-backed plugins of Oliani et al.):

* ``storage`` — the dtype the DIA bands and the Krylov vectors of the
  *inner* sweep are held in (what the SpMV/axpy kernels stream from HBM);
* ``accum`` — the dtype the dot-product partials accumulate in (kernels
  upcast per element, so a bf16 sweep still reduces in f32);
* ``refine`` — whether an **outer f64 iterative-refinement loop** wraps
  the inner sweep: replay the true residual ``r = b - A_hi x`` in f64,
  solve the *correction* system ``A_lo d = r`` in low precision to a
  loose ``inner_tol``, apply ``x += d`` in f64, repeat.  Each outer pass
  contracts the f64 error by roughly ``inner_tol + O(eps_storage *
  cond)``, so the converged answer meets the repo-wide <=1e-10
  final-answer parity gate *by construction* — the low precision only
  ever touches a correction, never the accumulated solution.

The policy travels end-to-end: ``SolverOps`` carries it into the solver
bodies, ``SegregatedSolver``/``PlanCache`` key compiled programs on it,
the cost model prices its bytes/iter, and the serving engine splits
cohorts and escalates ``bf16_ir -> f32_ir -> f64`` on supervisor faults.

This module is deliberately jnp-light (names + itemsizes are plain
Python) so :mod:`repro.core.cost_model` can price policies without
touching JAX; :attr:`PrecisionPolicy.storage_dtype` resolves lazily.
"""
from __future__ import annotations

import dataclasses

__all__ = [
    "PrecisionPolicy", "F64", "F32_IR", "BF16_IR", "POLICIES",
    "PRECISION_FALLBACK", "get_policy",
]


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """One named point on the storage-precision / refinement trade."""

    name: str
    storage: str          # dtype name for bands + inner-sweep vectors
    accum: str            # dtype name for dot-partial accumulation
    storage_itemsize: int  # bytes/value streamed by the inner hot loop
    accum_itemsize: int    # bytes/value of a partial-sum slot
    refine: bool          # outer f64 residual-replay loop around the sweep
    inner_tol: float      # relative tolerance of one inner correction solve
    max_outer: int        # outer-refinement cadence cap

    @property
    def storage_dtype(self):
        """The storage dtype as a jnp dtype (lazy: keeps this module
        importable without JAX for cost-model arithmetic)."""
        import jax.numpy as jnp

        return jnp.dtype(self.storage)

    @property
    def accum_dtype(self):
        import jax.numpy as jnp

        return jnp.dtype(self.accum)


# The do-nothing policy: everything f64, no outer loop — the plain
# solver (all casts are no-ops).
F64 = PrecisionPolicy(name="f64", storage="float64", accum="float64",
                      storage_itemsize=8, accum_itemsize=8,
                      refine=False, inner_tol=0.0, max_outer=0)

# f32 storage halves every band/vector byte; f32 accumulation is ample for
# the block partials (the outer loop absorbs the rest).  One inner sweep
# reliably reaches 1e-4, so ~3-4 outers cover a 1e-12 pressure tolerance.
F32_IR = PrecisionPolicy(name="f32_ir", storage="float32", accum="float32",
                         storage_itemsize=4, accum_itemsize=4,
                         refine=True, inner_tol=1e-4, max_outer=16)

# bf16 storage quarters the bytes but eps ~= 4e-3 floors what one sweep
# can contract: the inner tolerance stays above the bf16 stagnation level
# (5e-2 >> eps) so every sweep terminates fast, and the generous outer cap
# still reaches 1e-12 at ~6e-2 contraction per outer.  Partials accumulate
# in f32 (a bf16 reduction over 2048-row blocks would lose the dot).
BF16_IR = PrecisionPolicy(name="bf16_ir", storage="bfloat16", accum="float32",
                          storage_itemsize=2, accum_itemsize=4,
                          refine=True, inner_tol=5e-2, max_outer=48)

POLICIES: dict[str, PrecisionPolicy] = {
    p.name: p for p in (F64, F32_IR, BF16_IR)
}

# The supervisor's escalation ladder: one rung toward f64 per fault, tried
# *before* any backend rebind (repro.serving.engine._supervise).
PRECISION_FALLBACK: dict[str, str] = {"bf16_ir": "f32_ir", "f32_ir": "f64"}


def get_policy(precision: str | PrecisionPolicy) -> PrecisionPolicy:
    """Resolve a policy name (or pass a policy through), raising on typos."""
    if isinstance(precision, PrecisionPolicy):
        return precision
    try:
        return POLICIES[precision]
    except KeyError:
        raise ValueError(
            f"unknown precision policy {precision!r}; "
            f"expected one of {tuple(POLICIES)}") from None
