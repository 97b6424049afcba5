"""Preconditioned conjugate gradients over a pluggable SolverOps backend.

Mirrors Ginkgo's CG used for the paper's pressure solves.  The solver body
is written against :class:`repro.solvers.ops.SolverOps`, so one control
flow serves the stacked, single-device and full-mesh layouts and both the
reference-jnp and fused-Pallas per-iteration backends (the legacy
``cg(A, b, x0, M=...)`` closure signature still works and wraps into the
reference backend).  All reductions are global, lowering to all-reduce
over the sharded part axes; control flow is ``lax.while_loop`` so the
solver jits into a single XLA computation (no host round-trips per
iteration — the device-resident equivalent of the paper keeping the solve
on the GPU).

The squared residual norm ``r . r`` is **carried in the loop state**: the
``fused_step``/body computes it once per iteration and ``cond`` compares
the carried value against the squared threshold, instead of re-issuing a
dot (an extra global all-reduce per iteration) in both ``cond`` and
``body`` as the seed did.

**Iterative refinement.**  When the bundle's
:class:`repro.solvers.precision.PrecisionPolicy` refines (``f32_ir`` /
``bf16_ir``), the while_loop above becomes the *inner sweep* of an outer
f64 loop: replay the true residual ``r = b - A_hi x`` in f64, solve the
correction system ``A_lo d = r`` with one low-precision sweep to the
policy's loose ``inner_tol``, apply ``x += omega d`` in f64, repeat until
the caller's f64 tolerance holds.  The outer ``cond`` compares the
carried f64 ``r.r``, and the exit flags keep the exact health signature
of the plain path (NaN anywhere => ``converged`` and ``hit_cap`` both
False).  What keeps the low-precision sweeps useful at deployment size:

* ``A_lo`` is the operator in *difference form*
  (:func:`repro.sparse.distributed.difference_form`, built by the
  backends): rounding the plain bands one by one perturbs the row sums,
  which for the all-Neumann pressure matrix add up over the mesh and at
  the 210³ cavity change ``1ᵀ A 1`` in f32 by 60% (the sweeps then
  diverge);
* each outer pass first solves for the constant vector in f64 (a
  Galerkin step) and the sweeps deflate it — the reference-pinned
  pressure matrix is nearly singular along it;
* the correction rhs is scaled to unit norm before the downcast (no
  subnormal products, which a TPU flushes to zero);
* a sweep whose curvature ``p . Ap`` stops being positive keeps its last
  iterate and ends, and ``omega`` is the f64 step length that minimizes
  the A-norm error along ``d`` — a poor (say, capped) sweep shortens the
  step instead of spoiling ``x``.

Per inner iteration a plain sweep all-reduces twice (``p . Ap``, then
``r . z`` with ``r . r``); a deflated sweep adds ``a1 . z``.  Per outer
pass the f64 loop adds two f64 SpMVs (``A d`` and the replay).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.solvers.ops import SolverOps, reference_ops, vdot

__all__ = ["cg", "CGResult"]


class CGResult(NamedTuple):
    x: jax.Array
    iters: jax.Array      # total inner Krylov iterations
    residual: jax.Array   # final ||r||_2 (f64 true residual when refined)
    converged: jax.Array  # bool: ||r|| <= threshold at exit (False on NaN)
    hit_cap: jax.Array    # bool: exited at an iteration cap w/o converging
    outer_iters: jax.Array = 0  # refinement passes (0 on the f64 policy)


def _cg_sweep(ops: SolverOps, b, x0, threshold_sq, maxiter, deflate=None):
    """One preconditioned-CG while_loop at the bundle's storage dtype.

    Returns ``(x, rr, k)`` with ``rr`` the carried squared residual norm
    (accum dtype) and ``k`` the iteration count.  The f64 policy runs it
    once as the whole solve; a refined policy runs it as the inner
    sweep.  The sweep runs in the bundle's vector layout (``ops.pack``
    in, ``ops.unpack`` out).

    ``deflate = (a1, inv_e)`` runs deflated CG (Saad et al. 2000) on the
    constant vector ``w``: ``a1 = A w`` (outer layout) and ``inv_e =
    1 / (wᵀ A w)``; each search direction drops its ``A``-component along
    ``w``, ``p = z - inv_e (a1 . z) w + beta p`` (``w`` is all ones, so
    that is a scalar subtraction: one more dot per iteration, no more
    vectors).  The caller makes the residual orthogonal to ``w`` first
    (see :func:`_cg_refined`).
    """
    if deflate is not None:
        a1, inv_e = deflate
        a1 = ops.pack(a1.astype(b.dtype))
    b, x0 = ops.pack(b), ops.pack(x0)

    def direction(z, p, beta):
        if deflate is not None:
            (az,) = ops.dots((a1, z))
            z = z - (inv_e * az).astype(z.dtype)
        return z if p is None else z + beta.astype(z.dtype) * p

    r0 = b - ops.matvec(x0)
    z0 = ops.precond(r0)
    gamma0, rr0 = ops.dots((r0, z0), (r0, r0))

    def cond(state):
        _, _, _, _, rr, k, brk = state
        return (rr > threshold_sq) & (k < maxiter) & ~brk

    def body(state):
        x, r, p, gamma, rr, k, _ = state
        Ap, pAp = ops.matvec_dot(p)
        alpha = gamma / pAp
        xn, rn, z, gamma_new, rr_new = ops.fused_step(x, r, p, Ap, alpha)
        beta = gamma_new / gamma
        pn = direction(z, p, beta)
        if deflate is None:
            return (xn, rn, pn, gamma_new, rr_new, k + 1, False)
        # a low-precision sweep whose curvature p . Ap is no longer
        # positive has hit its rounding floor: keep the last iterate and
        # stop (the f64 outer loop takes over from there)
        brk = ~(pAp > 0)
        keep = lambda old, new: jnp.where(brk, old, new)  # noqa: E731
        return (keep(x, xn), keep(r, rn), keep(p, pn),
                keep(gamma, gamma_new), keep(rr, rr_new), k + 1, brk)

    init = (x0, r0, direction(z0, None, None), gamma0, rr0,
            jnp.array(0, jnp.int32), jnp.array(False))
    x, r, _, _, rr, k, _ = jax.lax.while_loop(cond, body, init)
    return ops.unpack(x), rr, k


def _cg_refined(ops: SolverOps, b, x0, *, tol, atol, maxiter) -> "CGResult":
    """Outer f64 refinement loop around low-precision inner sweeps."""
    pol = ops.policy
    A_hi = ops.matvec_hi if ops.matvec_hi is not None else ops.matvec
    lo = pol.storage_dtype

    bb = vdot(b, b)
    threshold_sq = jnp.maximum(tol * jnp.sqrt(bb), atol) ** 2
    inner_tol_sq = pol.inner_tol ** 2

    def residual(x):
        r = b - A_hi(x)
        return r, vdot(r, r)

    r0, rr0 = residual(x0)
    # the constant vector w is (near-)null for the reference-pinned
    # all-Neumann pressure matrix: its smallest eigenvalue shrinks like
    # 1/cells, below the rounding of a low-precision product at the 210³
    # cavity.  So w is solved for in f64 here (a Galerkin step per outer
    # pass) and deflated out of the sweeps.
    a1 = A_hi(jnp.ones_like(b))
    inv_e = 1.0 / jnp.sum(a1)
    deflate = (a1, inv_e.astype(pol.accum_dtype))

    def cond(state):
        _, _, rr, k_out, _, _ = state
        return (rr > threshold_sq) & (k_out < pol.max_outer)

    def body(state):
        x, r, _, k_out, inner_total, inner_capped = state
        c = inv_e * jnp.sum(r)
        x, r = x + c, r - c * a1       # now w . r = 0
        # correction solve A_lo d = r at the storage dtype, from zero,
        # to the policy's loose relative tolerance; r is scaled to unit
        # norm first, since late outer residuals are small enough that
        # the sweep's products fall below the f32/bf16 normal range (a
        # TPU flushes those to zero)
        scale = jnp.sqrt(vdot(r, r))
        r_lo = (r / scale).astype(lo)
        (rr_lo,) = ops.dots((r_lo, r_lo))
        thr_lo = inner_tol_sq * rr_lo
        d, _, k_in = _cg_sweep(ops, r_lo, jnp.zeros_like(r_lo), thr_lo,
                               maxiter, deflate=deflate)
        # the correction enters at the step length that minimizes the f64
        # error in the A-norm along it, so no sweep (a capped one
        # included) can make x worse; a converged sweep gives omega ~ 1
        d = scale * d.astype(b.dtype)
        dAd = vdot(d, A_hi(d))
        omega = jnp.where(dAd > 0, vdot(d, r) / dAd, 0.0)
        x = x + omega * d
        r, rr = residual(x)   # f64 replay: low precision never touches x
        return (x, r, rr, k_out + 1, inner_total + k_in,
                inner_capped | (k_in >= maxiter))

    init = (x0, r0, rr0, jnp.array(0, jnp.int32), jnp.array(0, jnp.int32),
            jnp.array(False))
    x, _, rr, k_out, inner_total, inner_capped = jax.lax.while_loop(
        cond, body, init)
    converged = rr <= threshold_sq
    hit_cap = ((k_out >= pol.max_outer) | inner_capped) & ~converged
    return CGResult(x=x, iters=inner_total, residual=jnp.sqrt(rr),
                    converged=converged, hit_cap=hit_cap,
                    outer_iters=k_out)


def cg(A: Callable[[jax.Array], jax.Array] | SolverOps, b: jax.Array,
       x0: jax.Array, *, M: Callable[[jax.Array], jax.Array] | None = None,
       tol: float = 1e-8, atol: float = 0.0, maxiter: int = 1000) -> CGResult:
    """Solve ``A x = b`` (SPD) with preconditioned CG.

    ``A`` is either an operator closure (with ``M`` applying the
    preconditioner inverse, e.g. Jacobi ``r / diag``) or a ready-made
    :class:`SolverOps` bundle (``M`` must then be None).
    Convergence: ``||r|| <= max(tol * ||b||, atol)`` — always evaluated
    against the *true* f64 residual when the bundle's policy refines.
    ``maxiter`` caps the plain solve, or each inner sweep when refined.
    """
    if isinstance(A, SolverOps):
        assert M is None, "pass the preconditioner inside SolverOps"
        ops = A
    else:
        ops = reference_ops(A, M)

    if ops.policy.refine:
        return _cg_refined(ops, b, x0, tol=tol, atol=atol, maxiter=maxiter)

    (bb,) = ops.dots((b, b))
    threshold_sq = jnp.maximum(tol * jnp.sqrt(bb), atol) ** 2
    x, rr, k = _cg_sweep(ops, b, x0, threshold_sq, maxiter)
    # NaN rr compares False on both sides: converged and hit_cap both stay
    # False, which the health plumbing upstream reads as divergence.
    converged = rr <= threshold_sq
    hit_cap = (k >= maxiter) & ~converged
    return CGResult(x=x, iters=k, residual=jnp.sqrt(rr),
                    converged=converged, hit_cap=hit_cap,
                    outer_iters=jnp.zeros((), jnp.int32))
