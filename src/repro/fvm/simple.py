"""SIMPLE — the steady-state segregated program (simpleFoam).

Tomczak et al. (arXiv:1207.1571) ship PISO and SIMPLE as the two GPU
solvers of the same segregated family; the paper's repartitioning story
(fig. 5/7 assemble → update → solve decomposition) is identical for both.
This module is the proof that :class:`~repro.fvm.step_program.StepProgram`
really is program-agnostic: SIMPLE is a *different phase list over the
same phase toolkit* (``fvm/step_program._phase_toolkit``) plus an
outer-loop convergence predicate the executors iterate under
``lax.while_loop`` (``run_converged``).

One outer iteration:

1. **assemble_mom** — the steady momentum matrix.  The transient term is
   killed exactly by assembling with ``dt = inf`` (``V/dt = 0`` in IEEE
   arithmetic), so the shared assembly routine needs no steady variant.
2. **relax_mom** — implicit under-relaxation (OpenFOAM ``relax()``):
   ``diag' = diag / λ_u``, ``source' = source + (1-λ_u) diag' U`` — the
   relaxed system has the same fixed point but a diagonally-dominant
   matrix.  ``λ_u`` rides the env as a *traced* operand (``extra_keys``),
   so two tenants with different factors share one compilation.
3. **update_mom → solve_mom** — the toolkit's repartitioned BiCGStab.
4. **assemble_p → update_p → solve_p** — one pressure correction
   (``rAU`` built from the *relaxed* diagonal, per simpleFoam), CG with
   the previous pressure as the initial iterate.
5. **correct** — conservative flux correction with the *unrelaxed*
   ``p_new`` (mass conservation must see the full correction), explicit
   pressure relaxation ``p = p_old + λ_p (p_new - p_old)``, momentum
   correction from the relaxed gradient, and the two convergence
   residuals: the continuity error and ``u_delta = max|U - U_prev|``.

The program declares ``converged(stats)`` — both residuals under their
gates — which :meth:`FusedExecutor.run_converged` (and its vmapped cohort
variant) iterates to, capped at ``solver.max_outer``.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.fvm.step_program import (Phase, ProgramSpec, StepProgram,
                                    _phase_toolkit, health_flags,
                                    register_program)

__all__ = ["SimpleStats", "build_simple_program"]


class SimpleStats(NamedTuple):
    """Per-outer-iteration residuals (the convergence predicate's input).

    Field layout mirrors ``StepStats`` (mom_iters / p_iters / p_outer /
    continuity_err / p_residual) so serving-side consumers can treat the
    two uniformly, plus the outer velocity change ``u_delta``."""

    mom_iters: jax.Array
    p_iters: jax.Array         # (1,) — one correction per outer iteration
    p_outer: jax.Array         # (1,) — its f64 refinement passes
    continuity_err: jax.Array  # max |div(phi)| / V after correction
    p_residual: jax.Array
    u_delta: jax.Array         # max |U - U_prev| over the outer iteration
    # compiled health signals, same semantics as StepStats (see
    # step_program.health_flags)
    converged: jax.Array
    diverged: jax.Array
    hit_cap: jax.Array


def build_simple_program(solver) -> StepProgram:
    """Bind a :class:`~repro.fvm.piso.SegregatedSolver` into the SIMPLE
    phase list (see the module docstring for the iteration).

    The built program ignores the executor's ``dt`` operand (steady
    assembly uses ``dt = inf``) but keeps it in the signature so every
    program shares the executors' ``(state, dt, *extras)`` calling
    convention.  A padded (size-class) solver threads the usual
    ``n_active`` activity masks in front of the relaxation factors.
    """
    from repro.fvm.piso import PisoState

    tk = _phase_toolkit(solver)
    asm, mask_keys = tk.asm, tk.mask_keys
    dtype = solver.dtype
    tol_c = float(solver.tol_continuity)
    tol_u = float(solver.tol_u)

    def relax_mom(sysM, U, relax_u):
        diag = sysM.diag / relax_u
        source = sysM.source + ((1.0 - relax_u) * diag)[..., None] * U
        return dataclasses.replace(sysM, diag=diag, source=source)

    def correct(sysP, phiH, phiH_if, phiH_b, p, p_new, HbyA, rAU, relax_p,
                U0, *masks):
        a = tk.asm_of(*masks)
        # mass conservation sees the FULL pressure correction ...
        phi, phi_if = a.correct_flux(sysP, phiH, phiH_if, p_new)
        phi_b = a.correct_boundary_flux(sysP, phiH_b, p_new)
        # ... while the momentum correction uses the relaxed field
        p_rel = p + relax_p * (p_new - p)
        U = HbyA - rAU[..., None] * a.grad(p_rel)
        cont = jnp.max(jnp.abs(a.divergence(phi, phi_if, phi_b))) / a.V
        u_delta = jnp.max(jnp.abs(U - U0))
        return phi, phi_if, phi_b, p_rel, U, cont, u_delta

    phases = (
        Phase("assemble_mom", "assembly",
              ("U", "phi", "phi_if", "phi_b", "p", "dt") + mask_keys,
              ("sysM0",), tk.assemble_mom),
        Phase("relax_mom", "assembly", ("sysM0", "U", "relax_u"),
              ("sysM",), relax_mom),
        Phase("update_mom", "assembly", ("sysM",), ("bandsM",),
              tk.update_mom, instrumented_fn=tk.update_mom_inst),
        Phase("solve_mom", "assembly", ("bandsM", "sysM", "U"),
              ("U", "mom_iters", "mom_ok", "mom_cap"), tk.solve_mom),
        Phase("assemble_p", "assembly", ("sysM", "U") + mask_keys,
              ("rAU", "HbyA", "phiH", "phiH_if", "phiH_b", "sysP"),
              tk.assemble_p),
        Phase("update_p", "update", ("sysP",), ("bandsP",), tk.update_p,
              instrumented_fn=tk.update_p_inst),
        Phase("solve_p", "solve", ("bandsP", "sysP", "p"),
              ("p_new", "p_iters_0", "p_res", "p_ok_0", "p_cap_0",
               "p_outer_0"),
              tk.solve_p,
              probe=tk.halo_probe, probe_inputs=("p",),
              probe_iters="p_iters_0"),
        Phase("correct", "assembly",
              ("sysP", "phiH", "phiH_if", "phiH_b", "p", "p_new", "HbyA",
               "rAU", "relax_p", "U0") + mask_keys,
              ("phi", "phi_if", "phi_b", "p", "U", "cont", "u_delta"),
              correct),
    )

    # the steady timestep: assembling with dt = inf zeroes the transient
    # term exactly (V/inf = 0), so the executor's dt operand is ignored
    dt_inf = jnp.asarray(jnp.inf, dtype)

    if tk.padded:
        def seed(state, dt, n_active, relax_u, relax_p):
            U, p, phi, phi_if, phi_b = state
            if_mask, patch_mask = asm.dynamic_masks(n_active)
            return {"U": U, "p": p, "phi": phi, "phi_if": phi_if,
                    "phi_b": phi_b, "dt": dt_inf, "U0": U,
                    "relax_u": relax_u, "relax_p": relax_p,
                    "n_active": n_active, "if_mask": if_mask,
                    "patch_mask": patch_mask}

        seed_keys = ("U", "p", "phi", "phi_if", "phi_b", "dt", "U0",
                     "relax_u", "relax_p", "n_active", "if_mask",
                     "patch_mask")
        extra_keys = ("n_active", "relax_u", "relax_p")
    else:
        def seed(state, dt, relax_u, relax_p):
            U, p, phi, phi_if, phi_b = state
            return {"U": U, "p": p, "phi": phi, "phi_if": phi_if,
                    "phi_b": phi_b, "dt": dt_inf, "U0": U,
                    "relax_u": relax_u, "relax_p": relax_p}

        seed_keys = ("U", "p", "phi", "phi_if", "phi_b", "dt", "U0",
                     "relax_u", "relax_p")
        extra_keys = ("relax_u", "relax_p")

    def finalize(env):
        state = PisoState(env["U"], env["p"], env["phi"], env["phi_if"],
                          env["phi_b"])
        ok = env["mom_ok"] & env["p_ok_0"]
        cap = env["mom_cap"] | env["p_cap_0"]
        krylov_ok, diverged, hit_cap = health_flags(
            state, ok, cap, env["cont"], env["p_res"], env["u_delta"])
        stats = SimpleStats(
            mom_iters=env["mom_iters"],
            p_iters=jnp.stack([env["p_iters_0"]]),
            p_outer=jnp.stack([env["p_outer_0"]]),
            continuity_err=env["cont"],
            p_residual=env["p_res"],
            u_delta=env["u_delta"],
            converged=krylov_ok, diverged=diverged, hit_cap=hit_cap)
        return state, stats

    def converged(stats):
        return (stats.continuity_err < tol_c) & (stats.u_delta < tol_u)

    return StepProgram(phases=phases, seed=seed, finalize=finalize,
                       seed_keys=seed_keys, extra_keys=extra_keys,
                       converged=converged)


# pipelined stays at the ProgramSpec default (False): SIMPLE runs under
# run_converged's lax.while_loop, whose trip count is unknown until the
# convergence gates fire, so there is no static scan window to software-
# pipeline across — pipeline="auto" degrades to the serial fused
# executor and pipeline="on" raises ("no pipelined form").
register_program(ProgramSpec(
    name="simple",
    build=build_simple_program,
    transient=False,
    description=("steady-state SIMPLE: under-relaxed momentum + one "
                 "pressure correction per outer iteration, converged on "
                 "continuity + velocity-change gates (simpleFoam; "
                 "Tomczak et al. arXiv:1207.1571)"),
))
