"""BiCGStab for the (non-symmetric) momentum systems — OpenFOAM's choice.

Same conventions as :mod:`repro.solvers.cg`: the body runs over a
:class:`repro.solvers.ops.SolverOps` backend (or wraps legacy ``A``/``M``
closures into the reference one), global dots, ``lax.while_loop``, and the
squared residual norm carried in the loop state so ``cond`` adds no extra
all-reduce per iteration.  When the bundle's precision policy refines,
the while_loop becomes the inner sweep of the same outer f64
iterative-refinement loop as CG's: true-residual replay ``r = b - A_hi
x``, low-precision correction solve, f64 correction apply.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.solvers.ops import SolverOps, reference_ops, vdot

__all__ = ["bicgstab", "BiCGStabResult"]


class BiCGStabResult(NamedTuple):
    x: jax.Array
    iters: jax.Array      # total inner Krylov iterations
    residual: jax.Array
    converged: jax.Array  # bool: ||r|| <= threshold at exit (False on NaN)
    hit_cap: jax.Array    # bool: exited at an iteration cap w/o converging
    outer_iters: jax.Array = 0  # refinement passes (0 on the f64 policy)


def _safe_div(num, den):
    """num/den with 0 where den == 0 (breakdown guard, NaN-free in grad)."""
    ok = den != 0
    return jnp.where(ok, num / jnp.where(ok, den, 1.0), 0.0)


def _bicgstab_sweep(ops: SolverOps, b, x0, threshold_sq, maxiter):
    """One breakdown-guarded BiCGStab while_loop at the storage dtype.

    Returns ``(x, rr, k)``; the scalar carries (rho/alpha/omega/rr) live
    at the accum dtype of the bundle's dots, vector updates cast the
    scalars down per use — every cast is a no-op on the f64 policy, so
    there it is the plain solver body.
    The sweep runs in the bundle's vector layout (``ops.pack`` in,
    ``ops.unpack`` out).
    """
    b, x0 = ops.pack(b), ops.pack(x0)
    r0 = b - ops.matvec(x0)
    rhat = r0  # shadow residual
    (rr0,) = ops.dots((r0, r0))

    def cond(state):
        x, r, p, v, rho, alpha, omega, rr, k, brk = state
        return (rr > threshold_sq) & (k < maxiter) & ~brk

    def body(state):
        x, r, p, v, rho, alpha, omega, rr, k, brk = state
        (rho_new,) = ops.dots((rhat, r))
        beta = _safe_div(rho_new * alpha, rho * omega)
        p_new = r + beta.astype(r.dtype) * (p - omega.astype(r.dtype) * v)
        phat = ops.precond(p_new)
        v_new = ops.matvec(phat)
        (rv,) = ops.dots((rhat, v_new))
        alpha_new = _safe_div(rho_new, rv)
        a_lo = alpha_new.astype(r.dtype)
        s = r - a_lo * v_new
        shat = ops.precond(s)
        t = ops.matvec(shat)
        ts, tt = ops.dots((t, s), (t, t))
        omega_new = _safe_div(ts, tt)
        o_lo = omega_new.astype(r.dtype)
        x_new = x + a_lo * phat + o_lo * shat
        r_new = s - o_lo * t
        (rr_new,) = ops.dots((r_new, r_new))
        # rho or <rhat, v> hitting zero is a true breakdown: the step above
        # is no longer a Krylov update — keep the previous iterate and stop
        brk_new = (rho_new == 0) | (rv == 0)
        keep = lambda old, new: jnp.where(brk_new, old, new)
        return (keep(x, x_new), keep(r, r_new), keep(p, p_new),
                keep(v, v_new), keep(rho, rho_new), keep(alpha, alpha_new),
                keep(omega, omega_new), keep(rr, rr_new), k + 1, brk_new)

    one = jnp.ones((), rr0.dtype)
    init = (x0, r0, jnp.zeros_like(b), jnp.zeros_like(b), one, one, one,
            rr0, jnp.array(0, jnp.int32), jnp.array(False))
    x, r, *_, rr, k, _ = jax.lax.while_loop(cond, body, init)
    return ops.unpack(x), rr, k


def _bicgstab_refined(ops: SolverOps, b, x0, *, tol, atol,
                      maxiter) -> "BiCGStabResult":
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

    def cond(state):
        _, _, rr, k_out, _, _ = state
        return (rr > threshold_sq) & (k_out < pol.max_outer)

    def body(state):
        x, r, rr, k_out, inner_total, inner_capped = state
        # unit-norm correction rhs, as in repro.solvers.cg._cg_refined
        scale = jnp.sqrt(rr)
        r_lo = (r / scale).astype(lo)
        (rr_lo,) = ops.dots((r_lo, r_lo))
        thr_lo = inner_tol_sq * rr_lo
        d, _, k_in = _bicgstab_sweep(ops, r_lo, jnp.zeros_like(r_lo),
                                     thr_lo, maxiter)
        x = x + scale * d.astype(b.dtype)
        r, rr = residual(x)
        return (x, r, rr, k_out + 1, inner_total + k_in,
                inner_capped | (k_in >= maxiter))

    init = (x0, r0, rr0, jnp.array(0, jnp.int32), jnp.array(0, jnp.int32),
            jnp.array(False))
    x, _, rr, k_out, inner_total, inner_capped = jax.lax.while_loop(
        cond, body, init)
    converged = rr <= threshold_sq
    hit_cap = ((k_out >= pol.max_outer) | inner_capped) & ~converged
    return BiCGStabResult(x=x, iters=inner_total, residual=jnp.sqrt(rr),
                          converged=converged, hit_cap=hit_cap,
                          outer_iters=k_out)


def bicgstab(A: Callable[[jax.Array], jax.Array] | SolverOps, b: jax.Array,
             x0: jax.Array, *,
             M: Callable[[jax.Array], jax.Array] | None = None,
             tol: float = 1e-8, atol: float = 0.0,
             maxiter: int = 1000) -> BiCGStabResult:
    """Solve ``A x = b`` with preconditioned BiCGStab.

    Breakdown-guarded: when ``rho = <rhat, r>`` or ``<rhat, v>`` vanishes
    (Lanczos breakdown — e.g. an exact solve after one step, or ``b = 0``)
    the iteration terminates cleanly with the current iterate instead of
    dividing by zero inside ``lax.while_loop`` and poisoning the state with
    NaN.  ``<t, t> = 0`` means the stabilization residual is already exact;
    ``omega`` is then forced to 0, which reduces the update to the plain
    BiCG half-step (also NaN-free).

    On a refined precision policy the convergence test runs against the
    true f64 residual of the outer loop; ``maxiter`` then caps each inner
    sweep.
    """
    if isinstance(A, SolverOps):
        assert M is None, "pass the preconditioner inside SolverOps"
        ops = A
    else:
        ops = reference_ops(A, M)

    if ops.policy.refine:
        return _bicgstab_refined(ops, b, x0, tol=tol, atol=atol,
                                 maxiter=maxiter)

    (bb,) = ops.dots((b, b))
    threshold_sq = jnp.maximum(tol * jnp.sqrt(bb), atol) ** 2
    x, rr, k = _bicgstab_sweep(ops, b, x0, threshold_sq, maxiter)
    # NaN rr yields converged=False and hit_cap=False: the silent-maxiter
    # exit is now distinguishable from convergence AND from divergence.
    # (A breakdown exit before the cap reports converged=False too.)
    converged = rr <= threshold_sq
    hit_cap = (k >= maxiter) & ~converged
    return BiCGStabResult(x=x, iters=k, residual=jnp.sqrt(rr),
                          converged=converged, hit_cap=hit_cap,
                          outer_iters=jnp.zeros((), jnp.int32))
