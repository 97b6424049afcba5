"""Flow-case registry + SIMPLE steady-state program semantics.

The Program/Case abstraction's acceptance tests: case BC masks assemble
what they claim (inlet fixes the flux, outlet extrapolates it, global
mass balances exactly), the cavity legacy path stays bitwise-identical,
the outer-loop executor converges/caps as declared, SIMPLE's steady
answer agrees with a long-horizon PISO march, and both survive
size-class padding and cohort batching unchanged.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.fvm.assembly import CavityAssembly, DOWN, UP
from repro.fvm.cases import FlowCase, PatchBC, case_names, get_case
from repro.fvm.mesh import CavityMesh, PaddedCavityMesh
from repro.fvm.piso import PisoSolver, SimpleSolver, make_solver
from repro.fvm.step_program import get_program, program_names


# ---------------------------------------------------------------------------
# case registry
# ---------------------------------------------------------------------------

def test_registries_know_the_shipped_cases_and_programs():
    assert {"cavity", "channel", "backstep"} <= set(case_names())
    assert {"piso", "simple"} <= set(program_names())
    assert get_program("piso").transient
    assert not get_program("simple").transient
    with pytest.raises(KeyError, match="nope"):
        get_case("nope")
    with pytest.raises(KeyError, match="nope"):
        make_solver("nope", CavityMesh.cube(4, 2))


def test_get_case_reparameterizes_without_mutating_the_registry():
    base = get_case("channel")
    hot = get_case("channel", reynolds=500.0)
    assert hot.reynolds == 500.0 and hot.name == "channel"
    assert get_case("channel").reynolds == base.reynolds
    # nu derives from (u_ref, L, Re)
    assert hot.nu(0.1) == pytest.approx(hot.u_ref * 0.1 / 500.0)


def test_case_validation_rejects_malformed_bc_sets():
    with pytest.raises(ValueError):
        PatchBC("bogus", U=(0, 0, 1))         # unknown BC kind
    with pytest.raises(ValueError):
        PatchBC("wall", profile="upper_half")  # profiles are inlet-only
    with pytest.raises(ValueError):           # inlet must be a z-face
        FlowCase("bad", "x-inlet", bcs={
            "x0": PatchBC("inlet", U=(1, 0, 0)), "z1": PatchBC("outlet")})
    with pytest.raises(ValueError):           # inlet without an outlet
        FlowCase("bad", "no outlet", bcs={
            "z0": PatchBC("inlet", U=(0, 0, 1))})
    with pytest.raises(ValueError):           # unknown geometric role
        FlowCase("bad", "bad role", bcs={"q7": PatchBC("wall")})


# ---------------------------------------------------------------------------
# case-aware assembly masks
# ---------------------------------------------------------------------------

def test_cavity_case_path_is_bitwise_identical_to_legacy():
    """The explicit cavity FlowCase must not perturb the seed numerics:
    same moving-lid patch, zero boundary flux everywhere (all cavity
    patches are walls in the wall-normal direction), identical momentum
    and pressure systems."""
    mesh = CavityMesh.cube(4, 2)
    legacy = CavityAssembly(mesh, nu=0.01)
    cased = CavityAssembly(mesh, nu=0.01, case=get_case("cavity"))
    U = jnp.zeros((mesh.n_parts, mesh.n_cells, 3), jnp.float64)
    phi_b = cased.boundary_flux(U)
    assert float(jnp.abs(phi_b).max()) == 0.0

    phi = jnp.zeros((mesh.n_parts, mesh.n_faces), jnp.float64)
    phi_if = jnp.zeros((mesh.n_parts, 2, legacy.plane), jnp.float64)
    p = jnp.zeros((mesh.n_parts, mesh.n_cells), jnp.float64)
    a = legacy.assemble_momentum(U, phi, phi_if, p, 1e-3)
    b = cased.assemble_momentum(U, phi, phi_if, p, 1e-3, phi_b=phi_b)
    assert jnp.array_equal(a.diag, b.diag)
    assert jnp.array_equal(a.source, b.source)


def test_channel_boundary_flux_masks():
    """Inlet flux is the prescribed U_b . n A on the inlet plane only;
    the outlet plane extrapolates the interior velocity (zero-gradient),
    so at rest the outlet flux is zero."""
    mesh = CavityMesh.cube(4, 2)
    asm = CavityAssembly(mesh, nu=0.01, case=get_case("channel"))
    U = jnp.zeros((mesh.n_parts, mesh.n_cells, 3), jnp.float64)
    phi_b = np.asarray(asm.boundary_flux(U))
    A = mesh.h ** 2
    # inlet (z0 plane, slot DOWN, owned by part 0): phi = (U_b . n) A = -A
    assert np.allclose(phi_b[0, DOWN], -A)
    assert np.abs(phi_b[1, DOWN]).max() == 0.0   # patch_mask: part 0 only
    # total prescribed inflow is -A_inlet * w_in
    assert np.isclose(phi_b.sum(), -mesh.nx * mesh.ny * A)
    # outlet extrapolates: zero at rest everywhere on the UP slot
    assert np.abs(phi_b[:, UP]).max() == 0.0

    # a uniform interior velocity w=2 shows up at the outlet plane as
    # 2 * A per face — extrapolation, not prescription
    U2 = U.at[..., 2].set(2.0)
    phi_b2 = np.asarray(asm.boundary_flux(U2))
    assert np.allclose(phi_b2[-1, UP], 2.0 * A)
    assert np.abs(phi_b2[0, UP]).max() == 0.0    # last part owns z1
    assert np.allclose(phi_b2[0, DOWN], -A)      # inlet stays prescribed


def test_backstep_inlet_covers_the_upper_half():
    mesh = CavityMesh.cube(4, 2)
    asm = CavityAssembly(mesh, nu=0.01, case=get_case("backstep"))
    U = jnp.zeros((mesh.n_parts, mesh.n_cells, 3), jnp.float64)
    phi_b = np.asarray(asm.boundary_flux(U))
    # only the upper-half (y >= ny/2) inlet faces carry flux
    assert np.isclose(phi_b.sum(), -(mesh.nx * mesh.ny // 2) * mesh.h ** 2)


# ---------------------------------------------------------------------------
# outer-loop executor (run_steady / run_converged)
# ---------------------------------------------------------------------------

def test_run_steady_converges_under_the_cap_and_respects_it():
    solver = SimpleSolver(CavityMesh.cube(4, 2), alpha=2, nu=0.01)
    state, stats, n_outer = solver.run_steady()
    assert bool(solver.program.converged(stats))
    assert 1 < int(n_outer) < solver.max_outer
    assert float(stats.continuity_err) < solver.tol_continuity
    assert float(stats.u_delta) < solver.tol_u

    # the cap is a hard ceiling: 5 iterations cannot converge this flow
    _, stats5, n5 = solver.run_steady(max_outer=5)
    assert int(n5) == 5
    assert not bool(solver.program.converged(stats5))


def test_piso_has_no_convergence_predicate():
    """run_steady is a steady-program affordance; the transient PISO
    program must refuse it rather than loop forever."""
    solver = PisoSolver(CavityMesh.cube(4, 2), alpha=2)
    assert solver.program.converged is None
    with pytest.raises((ValueError, TypeError)):
        solver.run_steady()


def test_simple_agrees_with_long_horizon_piso_on_cavity():
    """The physics acceptance gate: SIMPLE's steady cavity answer matches
    a settled transient PISO march.  The PISO fixed point retains an
    O(dt) Rhie-Chow smoothing term, so agreement is a few percent of the
    lid speed, not machine epsilon (dt = 5e-3 gives 0.024 here; the gate
    is 0.05)."""
    mesh = CavityMesh.cube(4, 2)
    s_state, stats, _ = SimpleSolver(mesh, alpha=2, nu=0.01).run_steady()
    assert bool(stats.continuity_err < 1e-5)

    piso = PisoSolver(mesh, alpha=2, nu=0.01)
    p_state, _ = piso.run_steps(piso.initial_state(), 5e-3, 600)
    diff = float(jnp.abs(s_state.U - p_state.U).max())
    assert diff < 0.05, f"SIMPLE vs settled PISO max|dU| = {diff}"


def test_simple_channel_conserves_mass_globally():
    """At convergence the outlet carries exactly the prescribed inflow:
    sum(phi_b) == 0 to continuity tolerance (the conservative
    flux-correction acceptance for the Dirichlet-pressure outlet)."""
    solver = SimpleSolver(CavityMesh.cube(4, 2), alpha=2, nu=0.01,
                          case="channel")
    state, stats, _ = solver.run_steady()
    assert bool(solver.program.converged(stats))
    net = float(jnp.sum(state.phi_b))
    inflow = 4 * 4 * solver.mesh.h ** 2
    # net boundary flux at convergence = the pressure-CG residual scale
    assert abs(net) < 1e-8 * inflow
    # and the flow actually goes somewhere: positive outlet flux
    assert float(jnp.sum(jnp.maximum(state.phi_b, 0.0))) > 0.5 * inflow


# ---------------------------------------------------------------------------
# padding + cohort batching keep case/program semantics
# ---------------------------------------------------------------------------

def test_padded_simple_case_matches_unpadded():
    """A size-class-padded SIMPLE session is the same fixed point: ghost
    slabs stay exactly zero and the real slabs match the unpadded run."""
    real = CavityMesh(nx=4, ny=4, nz=4, n_parts=2, h=0.025)
    solo_state, _, solo_n = SimpleSolver(real, alpha=1, nu=0.01,
                                         case="channel").run_steady()
    padded = SimpleSolver(PaddedCavityMesh.pad(real, 4), alpha=1, nu=0.01,
                          case="channel")
    pad_state, _, pad_n = padded.run_steady()
    assert int(pad_n) == int(solo_n)
    np.testing.assert_allclose(np.asarray(pad_state.U[:2]),
                               np.asarray(solo_state.U), atol=1e-12)
    assert float(jnp.abs(pad_state.U[2:]).max()) == 0.0


def test_batched_run_converged_matches_solo_per_lane():
    """The cohort (vmapped) while-loop must preserve every lane's exact
    outer-iteration count: converged lanes freeze while stragglers keep
    iterating (the batching rule dispatches until all predicates drop)."""
    from repro.fvm.piso import stack_states

    mesh = CavityMesh.cube(4, 2)
    factors = [(0.7, 0.3), (0.5, 0.5)]
    solos, outs = [], []
    for ru, rp in factors:
        s = SimpleSolver(mesh, alpha=2, nu=0.01, relax_u=ru, relax_p=rp)
        st, _, n = s.run_steady()
        solos.append(st)
        outs.append(int(n))
    assert outs[0] != outs[1]  # genuinely heterogeneous convergence

    lead = SimpleSolver(mesh, alpha=2, nu=0.01, relax_u=factors[0][0],
                        relax_p=factors[0][1])
    others = [SimpleSolver(mesh, alpha=2, nu=0.01, relax_u=ru, relax_p=rp)
              for ru, rp in factors[1:]]
    states = stack_states([s.initial_state()
                           for s in [lead] + others])
    per_lane = [s._extras() for s in [lead] + others]
    extras = tuple(jnp.stack(col) for col in zip(*per_lane))
    dts = jnp.ones(len(factors), lead.dtype)
    bstate, _, n_outer = lead.batched_executor(len(factors)).run_converged(
        states, dts, lead.max_outer, *extras)
    assert [int(k) for k in n_outer] == outs
    for i, solo in enumerate(solos):
        np.testing.assert_allclose(np.asarray(bstate.U[i]),
                                   np.asarray(solo.U), atol=1e-9)
