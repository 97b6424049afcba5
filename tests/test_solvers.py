"""Krylov solvers + distributed SpMV against dense references."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.ldu import LDULayout, buffer_from_parts
from repro.core.repartition import plan_for_mesh
from repro.core.update import update_device_direct, update_host_buffer
from repro.fvm.mesh import CavityMesh
from repro.solvers.cg import cg
from repro.solvers.bicgstab import bicgstab
from repro.solvers.jacobi import jacobi_preconditioner
from repro.sparse.distributed import spmv_dia, spmv_ell

from helpers import global_dense


def laplacian_buffers(mesh):
    """SPD 7-point Laplacian (+I to regularize) as stacked LDU buffers."""
    layout = LDULayout.from_mesh(mesh)
    P = mesh.n_parts
    diag = np.zeros((P, layout.n_cells))
    upper = -np.ones((P, layout.n_faces))
    lower = -np.ones((P, layout.n_faces))
    iface = -np.ones((P, layout.n_ifaces, layout.iface_size))
    iface *= mesh.iface_mask()[:, :, None]
    # diag = -(row sum of offdiag) + 1
    for part in range(P):
        np.add.at(diag[part], layout.owner, 1.0)
        np.add.at(diag[part], layout.neigh, 1.0)
        for s in range(layout.n_ifaces):
            np.add.at(diag[part], layout.iface_rows[s],
                      np.abs(iface[part, s]))
    diag += 1.0
    return layout, buffer_from_parts(diag, upper, lower, iface), diag


def _ldu_offdiag(mesh, layout):
    """The unit-conductance off-diagonal LDU blocks of ``laplacian_buffers``."""
    P = mesh.n_parts
    iface = -np.ones((P, layout.n_ifaces, layout.iface_size))
    iface *= mesh.iface_mask()[:, :, None]
    return (-np.ones((P, layout.n_faces)), -np.ones((P, layout.n_faces)),
            iface)


@pytest.mark.parametrize("alpha", [1, 2, 4])
def test_spmv_matches_dense(alpha):
    mesh = CavityMesh.cube(4, 4)
    layout, buffers, _ = laplacian_buffers(mesh)
    A_dense = global_dense(layout, buffers)
    plan = plan_for_mesh(mesh, alpha)
    n_c = mesh.n_parts // alpha

    grouped = jnp.asarray(buffers).reshape(n_c, alpha, -1)
    bands = update_device_direct(plan, grouped, target="dia")
    vals_ell = update_device_direct(plan, grouped, target="ell")

    rng = np.random.default_rng(3)
    x = rng.standard_normal(mesh.n_cells_global)
    y_ref = A_dense @ x
    xs = jnp.asarray(x).reshape(n_c, plan.m_coarse)

    y_dia = spmv_dia(bands, xs, offsets=tuple(int(o) for o in plan.dia_offsets),
                     plane=plan.plane)
    np.testing.assert_allclose(np.asarray(y_dia).reshape(-1), y_ref, rtol=1e-12)

    y_ell = spmv_ell(vals_ell, jnp.asarray(plan.ell_cols), xs, plane=plan.plane)
    np.testing.assert_allclose(np.asarray(y_ell).reshape(-1), y_ref, rtol=1e-12)


def test_host_buffer_update_matches_device_direct():
    mesh = CavityMesh.cube(4, 4)
    _, buffers, _ = laplacian_buffers(mesh)
    plan = plan_for_mesh(mesh, 2)
    grouped = jnp.asarray(buffers).reshape(2, 2, -1)
    a = update_device_direct(plan, grouped, target="dia")
    b = update_host_buffer(plan, grouped, target="dia")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("alpha", [1, 2])
def test_cg_solves_spd_system(alpha):
    mesh = CavityMesh.cube(4, 2)
    layout, buffers, diag = laplacian_buffers(mesh)
    A_dense = global_dense(layout, buffers)
    plan = plan_for_mesh(mesh, alpha)
    n_c = mesh.n_parts // alpha
    grouped = jnp.asarray(buffers).reshape(n_c, alpha, -1)
    bands = update_device_direct(plan, grouped, target="dia")
    offsets = tuple(int(o) for o in plan.dia_offsets)

    def A(v):
        return spmv_dia(bands, v, offsets=offsets, plane=plan.plane)

    rng = np.random.default_rng(4)
    x_true = rng.standard_normal(mesh.n_cells_global)
    b = (A_dense @ x_true).reshape(n_c, plan.m_coarse)
    Mj = jacobi_preconditioner(jnp.asarray(diag).reshape(n_c, plan.m_coarse))
    res = cg(A, jnp.asarray(b), jnp.zeros_like(jnp.asarray(b)), M=Mj, tol=1e-12)
    np.testing.assert_allclose(np.asarray(res.x).reshape(-1), x_true,
                               rtol=0, atol=1e-7)
    assert int(res.iters) < 200


def test_bicgstab_solves_nonsymmetric_system():
    mesh = CavityMesh.cube(4, 2)
    layout, buffers, diag = laplacian_buffers(mesh)
    # skew the off-diagonals to make it non-symmetric (convection-like)
    rng = np.random.default_rng(5)
    b2 = np.array(buffers)
    segs = layout.segments()
    b2[:, segs["upper"]] *= 0.5
    A_dense = global_dense(layout, b2)
    plan = plan_for_mesh(mesh, 2)
    grouped = jnp.asarray(b2).reshape(1, 2, -1)
    bands = update_device_direct(plan, grouped, target="dia")
    offsets = tuple(int(o) for o in plan.dia_offsets)

    def A(v):
        return spmv_dia(bands, v, offsets=offsets, plane=plan.plane)

    x_true = rng.standard_normal(mesh.n_cells_global)
    b = (A_dense @ x_true).reshape(1, -1)
    Mj = jacobi_preconditioner(jnp.asarray(diag).reshape(1, -1))
    res = bicgstab(A, jnp.asarray(b), jnp.zeros_like(jnp.asarray(b)), M=Mj,
                   tol=1e-12, maxiter=500)
    np.testing.assert_allclose(np.asarray(res.x).reshape(-1), x_true,
                               rtol=0, atol=1e-6)


# ---- BiCGStab breakdown guards (regression: NaN inside lax.while_loop) ----

def test_bicgstab_b_zero_terminates_cleanly():
    """b = 0 makes the threshold 0; the exact-solve iterate must not NaN."""
    b = jnp.zeros((1, 8))
    x0 = jnp.ones((1, 8))
    res = bicgstab(lambda v: v, b, x0, tol=1e-12, maxiter=50)
    assert np.isfinite(np.asarray(res.x)).all()
    assert float(res.residual) == 0.0
    assert int(res.iters) <= 2


def test_bicgstab_exact_solve_in_one_step():
    """With A = I the first half-step is exact: s = t = 0 hits the
    <t, t> = 0 division — the guard must finish with the exact answer."""
    rng = np.random.default_rng(7)
    b = jnp.asarray(rng.standard_normal((1, 16)))
    res = bicgstab(lambda v: v, b, jnp.zeros_like(b), tol=1e-12, maxiter=50)
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(b), rtol=1e-14)
    assert int(res.iters) == 1
    assert np.isfinite(float(res.residual))


def test_bicgstab_orthogonal_breakdown_keeps_iterate_finite():
    """Rotation operator: <rhat, A p> = 0 in the first iteration (serious
    Lanczos breakdown).  Pre-guard this divided by zero and returned NaN;
    now the loop must stop with the last finite iterate."""
    R = jnp.asarray([[0.0, 1.0], [-1.0, 0.0]])

    def A(v):
        return v @ R.T

    b = jnp.asarray([[1.0, 0.0]])
    res = bicgstab(A, b, jnp.zeros_like(b), tol=1e-12, maxiter=50)
    assert np.isfinite(np.asarray(res.x)).all()
    assert np.isfinite(float(res.residual))
    assert int(res.iters) < 50  # terminated by the breakdown flag, not maxiter


# ---- mixed-precision iterative refinement (ISSUE 10) ----------------------

def _refined_reference_ops(policy, bands, diag_c, offsets, plane):
    """Reference bundle under ``policy`` — exactly what
    ``PisoSolver._solver_ops`` builds on the reference backend."""
    from repro.solvers.ops import stacked_reference_ops

    return stacked_reference_ops(bands, diag_c, offsets=offsets,
                                 plane=plane, policy=policy)


@pytest.mark.parametrize("solver", [cg, bicgstab])
def test_refined_reference_policies_meet_parity_gate(solver):
    """f32_ir / bf16_ir on the SPD laplacian: ≤ 1e-10 of the f64 answer,
    identical convergence verdicts, refinement visible in outer_iters."""
    mesh = CavityMesh.cube(4, 4)
    layout, buffers, diag = laplacian_buffers(mesh)
    A_dense = global_dense(layout, buffers)
    plan = plan_for_mesh(mesh, 2)
    n_c = mesh.n_parts // 2
    grouped = jnp.asarray(buffers).reshape(n_c, 2, -1)
    bands = update_device_direct(plan, grouped, target="dia")
    offsets = tuple(int(o) for o in plan.dia_offsets)
    diag_c = jnp.asarray(diag).reshape(n_c, plan.m_coarse)
    rng = np.random.default_rng(11)
    x_true = rng.standard_normal(mesh.n_cells_global)
    b = jnp.asarray((A_dense @ x_true).reshape(n_c, plan.m_coarse))
    b = b / jnp.linalg.norm(b)
    x0 = jnp.zeros_like(b)

    res = {}
    for pol in ("f64", "f32_ir", "bf16_ir"):
        ops = _refined_reference_ops(pol, bands, diag_c, offsets,
                                     plan.plane)
        res[pol] = solver(ops, b, x0, tol=1e-12, maxiter=500)
    assert bool(res["f64"].converged) and int(res["f64"].outer_iters) == 0
    x64 = np.asarray(res["f64"].x)
    for pol in ("f32_ir", "bf16_ir"):
        r = res[pol]
        assert bool(r.converged) and not bool(r.hit_cap), pol
        assert int(r.outer_iters) >= 1, pol
        diff = float(np.max(np.abs(np.asarray(r.x) - x64)))
        assert diff <= 1e-10, (pol, diff)
        # the low-precision iterate really was computed at low precision:
        # more total inner iterations than the straight f64 solve
        assert int(r.iters) >= int(res["f64"].iters), pol


@pytest.mark.parametrize("precision", ["f64", "f32_ir"])
def test_step_stats_p_outer_is_the_cg_refinement_passes(precision,
                                                        monkeypatch):
    """``StepStats.p_outer`` holds each corrector's pressure-CG
    ``outer_iters``, in corrector order: the f64 refinement passes under
    ``f32_ir``, 0 under ``f64``."""
    import importlib

    from repro.fvm.piso import PisoSolver

    cg_module = importlib.import_module("repro.solvers.cg")

    seen = []
    real = cg_module.cg

    def spy(*args, **kw):
        res = real(*args, **kw)
        jax.debug.callback(lambda k: seen.append(int(k)), res.outer_iters,
                           ordered=True)
        return res

    monkeypatch.setattr(cg_module, "cg", spy)
    solver = PisoSolver(CavityMesh.cube(4, 2), alpha=2, precision=precision)
    _, stats = solver.step(solver.initial_state(), 1e-3)
    jax.block_until_ready(stats)
    jax.effects_barrier()
    assert stats.p_outer.shape == (solver.n_correctors,)
    assert stats.p_outer.tolist() == seen
    if precision == "f64":
        assert seen == [0] * solver.n_correctors
    else:
        assert min(seen) >= 1


@pytest.mark.parametrize("policy", ["f32_ir", "bf16_ir"])
def test_refined_cg_on_reference_pinned_neumann_laplacian(policy):
    """The pressure matrix shape: an all-Neumann Laplacian made definite
    by boosting one reference cell.  Its smallest eigenvalue shrinks like
    1/cells, so low-precision sweeps amplify the constant mode; with it
    deflated out of the sweeps (and solved in f64) the refined answer
    meets the f64 parity gate."""
    mesh = CavityMesh.cube(16, 2)
    layout, buffers, diag = laplacian_buffers(mesh)
    diag = diag - 1.0                        # drop the +I: all-Neumann
    diag[0, 0] *= 2.0                        # reference-cell boost
    buffers = buffer_from_parts(diag, *_ldu_offdiag(mesh, layout))
    plan = plan_for_mesh(mesh, 2)
    bands = update_device_direct(plan, jnp.asarray(buffers).reshape(1, 2, -1),
                                 target="dia")
    offsets = tuple(int(o) for o in plan.dia_offsets)
    diag_c = jnp.asarray(diag).reshape(1, -1)
    rng = np.random.default_rng(12)
    b = jnp.asarray(rng.standard_normal((1, plan.m_coarse)))
    b = b / jnp.linalg.norm(b)
    res = {pol: cg(_refined_reference_ops(pol, bands, diag_c, offsets,
                                          plan.plane),
                   b, jnp.zeros_like(b), tol=1e-12, maxiter=2000)
           for pol in ("f64", policy)}
    assert bool(res["f64"].converged)
    r = res[policy]
    assert bool(r.converged) and not bool(r.hit_cap)
    diff = float(np.max(np.abs(np.asarray(r.x) - np.asarray(res["f64"].x))))
    assert diff <= 1e-10 * float(np.max(np.abs(np.asarray(res["f64"].x))))


def _pinned_neumann_dia(n, parts):
    """The 7-band pressure matrix of an n³ cavity at uniform rAU — the
    all-Neumann Laplacian with the reference cell's diagonal doubled, as
    ``assemble_pressure_matrix`` builds it — stacked over z-slab parts."""
    plane = n * n
    m = plane * (n // parts)
    g = np.arange(parts * m)
    z, y, x = g // plane, (g % plane) // n, g % n
    h = 1.0 / n
    has = [z > 0, y > 0, x > 0, None, x < n - 1, y < n - 1, z < n - 1]
    bands = np.stack([np.zeros(g.size) if ok is None else -h * ok
                      for ok in has])
    bands[3] = -bands.sum(0)
    bands[3, 0] *= 2.0
    offsets = (-plane, -n, -1, 0, 1, n, plane)
    return (jnp.asarray(bands.reshape(7, parts, m).transpose(1, 0, 2)),
            offsets, plane)


def test_refined_cg_pinned_neumann_laplacian_at_scale():
    """At 80³ in f32 the rounded plain-form bands leave row sums whose
    total rivals the reference cell's pin, and the refined sweeps
    diverged; in difference form f32_ir meets the f64 tolerance in a few
    outer passes.  The rhs is mean-free, like a divergence source."""
    bands, offsets, plane = _pinned_neumann_dia(80, 5)
    diag = bands[:, 3, :]
    P, _, m = bands.shape
    zz = (np.arange(P * m) // plane).reshape(P, m) / 80
    rng = np.random.default_rng(14)
    b = 1e-3 * rng.standard_normal((P, m)) + np.exp(-((zz - 1) / .05) ** 2)
    b = jnp.asarray(b - b.mean())
    res = {pol: jax.jit(lambda b, pol=pol: cg(
        _refined_reference_ops(pol, bands, diag, offsets, plane), b,
        jnp.zeros_like(b), tol=1e-8, maxiter=2000))(b)
        for pol in ("f64", "f32_ir")}
    assert bool(res["f64"].converged)
    r = res["f32_ir"]
    assert bool(r.converged) and not bool(r.hit_cap)
    assert 1 <= int(r.outer_iters) <= 4
    assert int(r.iters) <= 2 * int(res["f64"].iters)
