"""Fused Krylov backend: kernel parity, solver parity, ragged tails, and
the no-reduction-in-cond regression (ISSUE 3)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.controller import PlanCache
from repro.core.cost_model import CostModel, TPU_V5E
from repro.core.repartition import plan_for_mesh
from repro.core.update import update_device_direct
from repro.fvm.mesh import CavityMesh
from repro.kernels.krylov_fused.krylov_fused import (
    fused_axpy_precond_single, pick_block_rows, spmv_dot_single)
from repro.kernels.krylov_fused.ops import fused_matvec_dot, fused_update_step
from repro.kernels.krylov_fused.ref import (fused_axpy_precond_ref,
                                            spmv_dot_ref)
from repro.kernels.spmv_dia.ops import spmv_dia_pallas
from repro.kernels.spmv_dia.ref import spmv_dia_ref
from repro.kernels.spmv_dia.spmv_dia import (DEFAULT_BLOCK_ROWS,
                                             lane_layout, spmv_dia_single,
                                             sublanes)
from repro.solvers.bicgstab import bicgstab
from repro.solvers.cg import cg
from repro.solvers.jacobi import jacobi_preconditioner
from repro.solvers.ops import (FUSED_MIN_ROWS, fused_stacked_ops,
                               reference_ops, resolve_backend)
from repro.sparse.distributed import (difference_form, spmv_dia, x_pad,
                                      x_pad_lanes)

from helpers import global_dense
from test_solvers import laplacian_buffers


# ---------------------------------------------------------------------------
# kernels vs oracles (interpret mode), including ragged row counts
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,plane,block", [
    (4096, 256, 512),    # block-aligned
    (777, 16, 256),      # ragged tail
    (100, 8, 2048),      # single padded block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_spmv_dot_kernel_vs_ref(m, plane, block, dtype):
    nx = max(plane // 4, 2)
    offsets = (-plane, -nx, -1, 0, 1, nx, plane)
    rng = np.random.default_rng(0)
    bands = jnp.asarray(rng.standard_normal((7, m)), dtype)
    xp = jnp.asarray(rng.standard_normal(m + 2 * plane), dtype)
    y_k, d_k = spmv_dot_single(bands, xp, offsets=offsets, plane=plane,
                               block_rows=block, interpret=True)
    y_r, d_r = spmv_dot_ref(bands, xp, offsets=offsets, plane=plane)
    tol = 1e-5 if dtype == jnp.float32 else 1e-12
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=tol,
                               atol=tol)
    np.testing.assert_allclose(float(d_k), float(d_r), rtol=10 * tol)


@pytest.mark.parametrize("m,block", [(4096, 512), (777, 256), (63, 2048)])
def test_fused_axpy_precond_kernel_vs_ref(m, block):
    rng = np.random.default_rng(1)
    vec = lambda: jnp.asarray(rng.standard_normal(m))
    x, r, p, Ap = vec(), vec(), vec(), vec()
    inv = jnp.asarray(1.0 / (1.0 + np.abs(rng.standard_normal(m))))
    alpha = jnp.asarray(0.37)
    outs_k = fused_axpy_precond_single(x, r, p, Ap, inv, alpha,
                                       block_rows=block, interpret=True)
    outs_r = fused_axpy_precond_ref(x, r, p, Ap, inv, alpha)
    for got, want in zip(outs_k, outs_r):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-12, atol=1e-12)


# ---------------------------------------------------------------------------
# ragged-tail SpMV (satellite: no m % block_rows assertion on the hot path)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,block", [(777, 256), (2049, 2048), (91, 64)])
def test_spmv_dia_single_ragged_tail(m, block):
    plane, nx = 16, 4
    offsets = (-plane, -nx, -1, 0, 1, nx, plane)
    rng = np.random.default_rng(2)
    bands = jnp.asarray(rng.standard_normal((7, m)))
    xp = jnp.asarray(rng.standard_normal(m + 2 * plane))
    y = spmv_dia_single(bands, xp, offsets=offsets, plane=plane,
                        block_rows=block, interpret=True)
    y_r = spmv_dia_ref(bands, xp, offsets=offsets, plane=plane)
    assert y.shape == (m,)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r), rtol=1e-12,
                               atol=1e-12)


def test_spmv_dia_pallas_stacked_odd_parts():
    """Stacked wrapper on a non-power-of-two part size (odd mesh x alpha)."""
    plane, nx, m, P = 9, 3, 243, 3   # 3^5 rows — no power-of-two factor
    offsets = (-plane, -nx, -1, 0, 1, nx, plane)
    rng = np.random.default_rng(3)
    bands = jnp.asarray(rng.standard_normal((P, 7, m)))
    x = jnp.asarray(rng.standard_normal((P, m)))
    y_ref = spmv_dia(bands, x, offsets=offsets, plane=plane)
    lay = lane_layout(m, plane, 64, bands.dtype)
    y = lay.unpack(spmv_dia_pallas(lay.pack(bands), lay.pack(x),
                                   offsets=offsets, layout=lay))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-12,
                               atol=1e-12)


@pytest.mark.parametrize("m,plane", [(243, 9), (4096, 256), (1000, 100)])
def test_x_pad_lanes_matches_flat_x_pad(m, plane):
    """The lane-dense x_pad built from packed parts equals the flat
    [down-halo | x | up-halo] laid out, for any lane remainder of m and
    plane; rows of x past m never leak in."""
    P = 3
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((P, m)))
    lay = lane_layout(m, plane, 1024, jnp.float32)
    packed = lay.pack(x)
    junk = packed.reshape(P, -1).at[:, m:].set(7.0).reshape(packed.shape)
    got = x_pad_lanes(junk, m=m, plane=plane, lead=lay.lead,
                      x_len=lay.x_len)
    want = jax.vmap(lay.pack_x_pad)(x_pad(x, plane))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_difference_form_is_the_same_operator():
    """difference_form moves the diagonal into row sums; the SpMV in
    difference form is the same operator (f64 round-off), and a constant
    vector maps exactly to the row sums."""
    plane, nx, m, P = 9, 3, 243, 3
    offsets = (-plane, -nx, -1, 0, 1, nx, plane)
    rng = np.random.default_rng(5)
    bands = jnp.asarray(rng.standard_normal((P, 7, m)))
    x = jnp.asarray(rng.standard_normal((P, m)))
    bd = difference_form(bands, offsets)
    np.testing.assert_allclose(
        np.asarray(spmv_dia(bd, x, offsets=offsets, plane=plane,
                            difference=True)),
        np.asarray(spmv_dia(bands, x, offsets=offsets, plane=plane)),
        rtol=1e-12, atol=1e-12)
    lo = bd.astype(jnp.float32)
    ones = jnp.ones((P, m), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(spmv_dia(lo, ones, offsets=offsets, plane=plane,
                            difference=True))[1:-1],
        np.asarray(lo[1:-1, 3]))  # interior parts: every neighbour is 1


@pytest.mark.parametrize("kernel", ["spmv_dia_single", "spmv_dot_single"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_band_kernels_difference_form_vs_ref(kernel, dtype, tol):
    m, plane, block, nx = 777, 16, 256, 4
    offsets = (-plane, -nx, -1, 0, 1, nx, plane)
    rng = np.random.default_rng(13)
    bands = jnp.asarray(rng.standard_normal((7, m)), dtype)
    xp = jnp.asarray(rng.standard_normal(m + 2 * plane), dtype)
    kw = dict(offsets=offsets, plane=plane, difference=True)
    y_r, d_r = spmv_dot_ref(bands, xp, accum_dtype="float32", **kw)
    if kernel == "spmv_dia_single":
        y_k = spmv_dia_single(bands, xp, block_rows=block, interpret=True,
                              accum_dtype="float32", **kw)
    else:
        y_k, d_k = spmv_dot_single(bands, xp, block_rows=block,
                                   interpret=True, accum_dtype="float32",
                                   **kw)
        np.testing.assert_allclose(float(d_k), float(d_r), rtol=10 * tol,
                                   atol=10 * tol)
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32), rtol=tol,
                               atol=tol)


def test_pick_block_rows():
    """Row blocks are whole (sublanes, 128) tiles of the lane-dense
    layout: 8 sublanes for 4-byte dtypes, 16 for bf16's packed ones."""
    assert pick_block_rows(1 << 20) == DEFAULT_BLOCK_ROWS
    assert pick_block_rows(DEFAULT_BLOCK_ROWS) == DEFAULT_BLOCK_ROWS
    assert pick_block_rows(200) == 1024    # one (8, 128) f32 tile
    assert pick_block_rows(1025) == 2048
    assert pick_block_rows(200, jnp.bfloat16) == 2048  # one (16, 128) tile
    for dt in (jnp.float32, jnp.bfloat16):
        assert pick_block_rows(1_852_200, dt) % (sublanes(dt) * 128) == 0


# ---------------------------------------------------------------------------
# fused backend == reference backend on a real repartitioned system
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("alpha", [1, 2, 4])
def test_cg_fused_matches_reference(alpha):
    mesh = CavityMesh.cube(4, 4)
    layout, buffers, diag = laplacian_buffers(mesh)
    A_dense = global_dense(layout, buffers)
    plan = plan_for_mesh(mesh, alpha)
    n_c = mesh.n_parts // alpha
    grouped = jnp.asarray(buffers).reshape(n_c, alpha, -1)
    bands = update_device_direct(plan, grouped, target="dia")
    offsets = tuple(int(o) for o in plan.dia_offsets)
    diag_c = jnp.asarray(diag).reshape(n_c, plan.m_coarse)

    rng = np.random.default_rng(4)
    x_true = rng.standard_normal(mesh.n_cells_global)
    b = jnp.asarray((A_dense @ x_true).reshape(n_c, plan.m_coarse))
    x0 = jnp.zeros_like(b)

    def A(v):
        return spmv_dia(bands, v, offsets=offsets, plane=plan.plane)

    ops_ref = reference_ops(A, jacobi_preconditioner(diag_c))
    ops_fus = fused_stacked_ops(bands, diag_c, offsets=offsets,
                                plane=plan.plane)
    res_ref = cg(ops_ref, b, x0, tol=1e-10)
    res_fus = cg(ops_fus, b, x0, tol=1e-10)
    # acceptance bar: <= 1e-10 with identical iteration counts
    assert int(res_ref.iters) == int(res_fus.iters)
    assert float(jnp.abs(res_fus.x - res_ref.x).max()) <= 1e-10
    np.testing.assert_allclose(np.asarray(res_fus.x).reshape(-1), x_true,
                               rtol=0, atol=1e-6)


def test_bicgstab_runs_on_fused_ops():
    """BiCGStab consumes the fused backend's matvec/precond members."""
    mesh = CavityMesh.cube(4, 2)
    layout, buffers, diag = laplacian_buffers(mesh)
    b2 = np.array(buffers)
    segs = layout.segments()
    b2[:, segs["upper"]] *= 0.5      # non-symmetric
    A_dense = global_dense(layout, b2)
    plan = plan_for_mesh(mesh, 2)
    grouped = jnp.asarray(b2).reshape(1, 2, -1)
    bands = update_device_direct(plan, grouped, target="dia")
    offsets = tuple(int(o) for o in plan.dia_offsets)
    diag_c = jnp.asarray(diag).reshape(1, -1)

    rng = np.random.default_rng(5)
    x_true = rng.standard_normal(mesh.n_cells_global)
    b = jnp.asarray((A_dense @ x_true).reshape(1, -1))
    ops_fus = fused_stacked_ops(bands, diag_c, offsets=offsets,
                                plane=plan.plane)
    res = bicgstab(ops_fus, b, jnp.zeros_like(b), tol=1e-12, maxiter=500)
    np.testing.assert_allclose(np.asarray(res.x).reshape(-1), x_true,
                               rtol=0, atol=1e-6)


def test_fused_matvec_dot_and_update_step_global_reductions():
    """Stacked wrappers reduce the block partials to exact global dots."""
    plane, nx, m, P = 8, 4, 160, 4
    offsets = (-plane, -nx, -1, 0, 1, nx, plane)
    rng = np.random.default_rng(6)
    bands = jnp.asarray(rng.standard_normal((P, 7, m)))
    x = jnp.asarray(rng.standard_normal((P, m)))
    y_ref = spmv_dia(bands, x, offsets=offsets, plane=plane)
    lay = lane_layout(m, plane, pick_block_rows(m), bands.dtype)
    y, d = fused_matvec_dot(lay.pack(bands), lay.pack(x), offsets=offsets,
                            layout=lay)
    np.testing.assert_allclose(np.asarray(lay.unpack(y)), np.asarray(y_ref),
                               rtol=1e-12)
    np.testing.assert_allclose(float(d), float(jnp.vdot(x, y_ref)),
                               rtol=1e-12)
    inv = jnp.asarray(1.0 / (1.0 + np.abs(rng.standard_normal((P, m)))))
    alpha = jnp.asarray(0.41)
    xn, rn, z, rz, rr = fused_update_step(
        *(lay.pack(v) for v in (x, x * 0.3, x * 0.2, y_ref, inv)), alpha,
        block_rows=lay.block_rows)
    rn = lay.unpack(rn)
    rn_ref = x * 0.3 - alpha * y_ref
    np.testing.assert_allclose(np.asarray(rn), np.asarray(rn_ref), rtol=1e-12)
    np.testing.assert_allclose(float(rr), float(jnp.vdot(rn_ref, rn_ref)),
                               rtol=1e-12)
    np.testing.assert_allclose(float(rz), float(jnp.vdot(rn_ref,
                                                         rn_ref * inv)),
                               rtol=1e-12)


# ---------------------------------------------------------------------------
# regression: zero-padded diagonal entries must not NaN-poison the solve
# ---------------------------------------------------------------------------
def test_fused_ops_zero_diag_safe_reciprocal():
    """``fused_stacked_ops`` guards its Jacobi inverse: zero diagonal
    entries (the ragged-tail zero padding) invert to 0, not inf — an
    unguarded ``1/diag`` made the first fused Jacobi apply compute
    ``inf * 0 = NaN`` in the padded lanes and poison the global dots."""
    diag = jnp.array([[2.0, 4.0, 0.0, 0.0]])   # two zero-padded rows
    bands = jnp.zeros((1, 3, 4)).at[:, 1, :].set(diag)
    ops = fused_stacked_ops(bands, diag, offsets=(-1, 0, 1), plane=1)
    r = ops.pack(jnp.array([[1.0, 1.0, 0.0, 0.0]]))
    z = ops.unpack(ops.precond(r))
    np.testing.assert_allclose(np.asarray(z), [[0.5, 0.25, 0.0, 0.0]])
    # the fused update step's dots stay finite too (r tail is exactly 0,
    # as the padding contract guarantees)
    zero = jnp.zeros_like(r)
    _, _, z2, rz, rr = ops.fused_step(zero, r, zero, zero, jnp.asarray(0.3))
    assert np.isfinite(np.asarray(z2)).all()
    assert np.isfinite(float(rz)) and np.isfinite(float(rr))


def test_cg_fused_ragged_zero_padded_part():
    """A zero-padded ragged part (size not divisible by ``block_rows``)
    solves to the reference solution with finite iterates: padded rows are
    all-zero (zero bands, zero rhs, zero diag), which is exactly the state
    the ragged-tail padding of PR 3 produces."""
    mesh = CavityMesh.cube(4, 2)
    layout, buffers, diag = laplacian_buffers(mesh)
    A_dense = global_dense(layout, buffers)
    plan = plan_for_mesh(mesh, 2)                 # one coarse part, m=128
    grouped = jnp.asarray(buffers).reshape(1, 2, -1)
    bands = update_device_direct(plan, grouped, target="dia")
    offsets = tuple(int(o) for o in plan.dia_offsets)

    rng = np.random.default_rng(7)
    x_true = rng.standard_normal(mesh.n_cells_global)
    b = (A_dense @ x_true).reshape(1, -1)

    pad = 37                                      # 128 + 37 = 165: ragged
    m_pad = plan.m_coarse + pad
    bands_p = jnp.asarray(np.pad(np.asarray(bands), ((0, 0), (0, 0),
                                                     (0, pad))))
    diag_p = jnp.asarray(np.pad(np.asarray(diag).reshape(1, -1),
                                ((0, 0), (0, pad))))
    b_p = jnp.asarray(np.pad(b, ((0, 0), (0, pad))))
    assert m_pad % 64 != 0 and float(diag_p[0, -1]) == 0.0

    ops = fused_stacked_ops(bands_p, diag_p, offsets=offsets,
                            plane=plan.plane, block_rows=64)
    res = cg(ops, b_p, jnp.zeros_like(b_p), tol=1e-10)
    x = np.asarray(res.x)
    assert np.isfinite(x).all(), "NaN-poisoned solve"
    np.testing.assert_allclose(x[0, :plan.m_coarse], x_true, rtol=0,
                               atol=1e-6)
    np.testing.assert_allclose(x[0, plan.m_coarse:], 0.0)   # padding inert

    # same solve through the reference backend (jacobi_preconditioner is
    # guarded by the same safe_jacobi_inverse): identical iteration counts
    def A(v):
        return spmv_dia(bands_p, v, offsets=offsets, plane=plan.plane)

    res_ref = cg(reference_ops(A, jacobi_preconditioner(diag_p)), b_p,
                 jnp.zeros_like(b_p), tol=1e-10)
    assert int(res.iters) == int(res_ref.iters)
    assert float(jnp.abs(res.x - res_ref.x).max()) <= 1e-10


# ---------------------------------------------------------------------------
# health flags: converged / hit_cap parity, reference vs fused (ISSUE 8)
# ---------------------------------------------------------------------------
def _spd_ops_pair(alpha=2):
    """The laplacian system of test_cg_fused_matches_reference through
    both backends, plus its rhs/x0."""
    mesh = CavityMesh.cube(4, 4)
    layout, buffers, diag = laplacian_buffers(mesh)
    A_dense = global_dense(layout, buffers)
    plan = plan_for_mesh(mesh, alpha)
    n_c = mesh.n_parts // alpha
    grouped = jnp.asarray(buffers).reshape(n_c, alpha, -1)
    bands = update_device_direct(plan, grouped, target="dia")
    offsets = tuple(int(o) for o in plan.dia_offsets)
    diag_c = jnp.asarray(diag).reshape(n_c, plan.m_coarse)
    rng = np.random.default_rng(8)
    x_true = rng.standard_normal(mesh.n_cells_global)
    b = jnp.asarray((A_dense @ x_true).reshape(n_c, plan.m_coarse))

    def A(v):
        return spmv_dia(bands, v, offsets=offsets, plane=plan.plane)

    ops_ref = reference_ops(A, jacobi_preconditioner(diag_c))
    ops_fus = fused_stacked_ops(bands, diag_c, offsets=offsets,
                                plane=plan.plane)
    return ops_ref, ops_fus, b, jnp.zeros_like(b)


@pytest.mark.parametrize("solver", [cg, bicgstab])
def test_krylov_flags_parity_reference_vs_fused(solver):
    """converged/hit_cap must agree across backends, both on a solve that
    converges and on one clamped below the iterations it needs."""
    ops_ref, ops_fus, b, x0 = _spd_ops_pair()
    res_ref = solver(ops_ref, b, x0, tol=1e-10)
    res_fus = solver(ops_fus, b, x0, tol=1e-10)
    assert bool(res_ref.converged) and bool(res_fus.converged)
    assert not bool(res_ref.hit_cap) and not bool(res_fus.hit_cap)
    assert int(res_ref.iters) == int(res_fus.iters)

    cap_ref = solver(ops_ref, b, x0, tol=1e-14, maxiter=2)
    cap_fus = solver(ops_fus, b, x0, tol=1e-14, maxiter=2)
    for res in (cap_ref, cap_fus):
        assert not bool(res.converged) and bool(res.hit_cap)
        assert int(res.iters) == 2
    # the capped residual is still reported (finite, nonzero)
    assert np.isfinite(float(cap_ref.residual))
    assert np.isfinite(float(cap_fus.residual))


@pytest.mark.parametrize("solver", [cg, bicgstab])
def test_krylov_flags_nan_rhs_signature(solver):
    """A NaN rhs is the divergence signature: the NaN residual makes the
    while-cond False immediately — 0 iterations, converged False AND
    hit_cap False (distinct from a capped solve)."""
    b = jnp.ones((2, 32)).at[0, 0].set(jnp.nan)
    res = solver(lambda v: 2.0 * v, b, jnp.zeros_like(b), tol=1e-10)
    assert int(res.iters) == 0
    assert not bool(res.converged) and not bool(res.hit_cap)


# ---------------------------------------------------------------------------
# regression: cond carries the residual norm — no reduction per check
# ---------------------------------------------------------------------------
_REDUCTIONS = {"dot_general", "reduce_sum", "reduce", "psum"}


def _count_reductions(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _REDUCTIONS:
            n += 1
        for val in eqn.params.values():
            sub = getattr(val, "jaxpr", None)
            if sub is not None:
                n += _count_reductions(sub)
            elif hasattr(val, "eqns"):
                n += _count_reductions(val)
    return n


def _while_eqn(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            return eqn
    raise AssertionError("no while_loop in solver jaxpr")


@pytest.mark.parametrize("solver", [cg, bicgstab])
def test_cond_adds_no_reduction(solver):
    b = jnp.ones((2, 32))
    jaxpr = jax.make_jaxpr(
        lambda b_, x0: solver(lambda v: 2.0 * v, b_, x0, tol=1e-10))(
        b, jnp.zeros_like(b))
    eqn = _while_eqn(jaxpr.jaxpr)
    cond = eqn.params["cond_jaxpr"].jaxpr
    body = eqn.params["body_jaxpr"].jaxpr
    assert _count_reductions(cond) == 0, cond
    assert _count_reductions(body) >= 1   # the dots live in the body


# ---------------------------------------------------------------------------
# backend selection + cost model + plan-cache keying
# ---------------------------------------------------------------------------
def test_resolve_backend():
    assert resolve_backend("fused", 8) == "fused"
    assert resolve_backend("reference", 1 << 22) == "reference"
    tpu = dict(on_tpu=True, precision="f32_ir")
    assert resolve_backend("auto", FUSED_MIN_ROWS, **tpu) == "fused"
    assert resolve_backend("auto", FUSED_MIN_ROWS - 1, **tpu) == "reference"
    # no f64 into a Pallas TPU kernel: auto keeps the XLA path, an
    # explicit fused request names the reason
    assert resolve_backend("auto", 1 << 22, on_tpu=True) == "reference"
    with pytest.raises(ValueError, match="f64"):
        resolve_backend("fused", 1 << 22, on_tpu=True)
    assert resolve_backend("fused", 1 << 22, on_tpu=False) == "fused"
    # off-TPU the kernels would run through the Pallas interpreter inside
    # the solve loop: auto never picks them (explicit "fused" still forces)
    assert resolve_backend("auto", 1 << 22, on_tpu=False) == "reference"
    # the bare probe must agree with the explicit flag for this host
    on_tpu = jax.default_backend() == "tpu"
    assert resolve_backend("auto", 1 << 22) == \
        resolve_backend("auto", 1 << 22, on_tpu=on_tpu)
    with pytest.raises(ValueError):
        resolve_backend("magic", 64)


def test_cost_model_fused_bytes_term():
    cm = CostModel(TPU_V5E, n_dofs=1e6)
    fused = cm.with_fused_solver(True)
    assert fused.solver_bytes() < cm.solver_bytes()
    ratio = cm.solver_bytes() / fused.solver_bytes()
    assert 1.2 <= ratio <= 1.6   # (7+8)/(7+5) = 1.25 at the defaults
    # the CPU baseline never runs fused kernels: unchanged
    assert fused.t_solver_cpu(8) == cm.t_solver_cpu(8)
    # device solve gets faster; alpha selection sees the new intensity
    assert fused.t_solve_core(4) < cm.t_solve_core(4)


def test_plan_cache_backend_key_component():
    mesh = CavityMesh.cube(4, 4)
    cache = PlanCache()
    p_auto = cache.plan_for_mesh(mesh, 2, "dia")
    p_fused = cache.plan_for_mesh(mesh, 2, "dia", backend="fused")
    p_fm_fused = cache.plan_for_mesh(mesh, 2, "dia", mode="full_mesh",
                                     backend="fused")
    assert cache.misses == 3 and cache.hits == 0
    assert cache.plan_for_mesh(mesh, 2, "dia", backend="fused") is p_fused
    assert cache.hits == 1
    # plans are structurally interchangeable; only the cache keys differ
    assert p_auto.m_coarse == p_fused.m_coarse == p_fm_fused.m_coarse


# ---------------------------------------------------------------------------
# mixed-precision policies (ISSUE 10): kernels, solvers, cost model, cache
# ---------------------------------------------------------------------------
from repro.solvers.precision import (F64, PRECISION_FALLBACK, get_policy,
                                     POLICIES)


def test_get_policy_validates_names():
    assert get_policy("f64") is F64
    assert get_policy(F64) is F64
    assert set(POLICIES) == {"f64", "f32_ir", "bf16_ir"}
    assert PRECISION_FALLBACK == {"bf16_ir": "f32_ir", "f32_ir": "f64"}
    with pytest.raises(ValueError, match="f16"):
        get_policy("f16")


@pytest.mark.parametrize("dtype,accum,tol", [
    (jnp.float32, "float64", 1e-5),
    (jnp.bfloat16, "float32", 2e-2),
])
def test_spmv_dot_kernel_low_precision_storage(dtype, accum, tol):
    """Low-precision loads + accum-dtype block partials: the kernel and
    its jnp oracle share the promotion contract, so they agree to the
    summation-order noise of the storage dtype."""
    m, plane, block = 777, 16, 256
    nx = 4
    offsets = (-plane, -nx, -1, 0, 1, nx, plane)
    rng = np.random.default_rng(10)
    bands = jnp.asarray(rng.standard_normal((7, m)), dtype)
    xp = jnp.asarray(rng.standard_normal(m + 2 * plane), dtype)
    y_k, d_k = spmv_dot_single(bands, xp, offsets=offsets, plane=plane,
                               block_rows=block, interpret=True,
                               accum_dtype=accum)
    y_r, d_r = spmv_dot_ref(bands, xp, offsets=offsets, plane=plane,
                            accum_dtype=accum)
    assert y_k.dtype == dtype and d_k.dtype == jnp.dtype(accum)
    np.testing.assert_allclose(np.asarray(y_k.astype(jnp.float64)),
                               np.asarray(y_r.astype(jnp.float64)),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(float(d_k), float(d_r), rtol=10 * tol,
                               atol=10 * tol)


def _fused_policy_system(policy, alpha=2):
    """The laplacian system of ``_spd_ops_pair`` as a fused bundle under
    ``policy``, with the rhs normalized so tol=1e-12 reaches an absolute
    error comparable across policies (the parity-gate methodology)."""
    mesh = CavityMesh.cube(4, 4)
    layout, buffers, diag = laplacian_buffers(mesh)
    A_dense = global_dense(layout, buffers)
    plan = plan_for_mesh(mesh, alpha)
    n_c = mesh.n_parts // alpha
    grouped = jnp.asarray(buffers).reshape(n_c, alpha, -1)
    bands = update_device_direct(plan, grouped, target="dia")
    offsets = tuple(int(o) for o in plan.dia_offsets)
    diag_c = jnp.asarray(diag).reshape(n_c, plan.m_coarse)
    rng = np.random.default_rng(8)
    x_true = rng.standard_normal(mesh.n_cells_global)
    b = jnp.asarray((A_dense @ x_true).reshape(n_c, plan.m_coarse))
    b = b / jnp.linalg.norm(b)
    ops = fused_stacked_ops(bands, diag_c, offsets=offsets,
                            plane=plan.plane, policy=policy)
    return ops, b


@pytest.mark.parametrize("solver", [cg, bicgstab])
def test_refined_policies_match_f64_within_gate(solver):
    """The acceptance gate: f32_ir and bf16_ir answers within 1e-10 of
    the f64 answer, same convergence verdict, refinement visible in
    ``outer_iters``."""
    res = {}
    for pol in ("f64", "f32_ir", "bf16_ir"):
        ops, b = _fused_policy_system(pol)
        res[pol] = solver(ops, b, jnp.zeros_like(b), tol=1e-12,
                          maxiter=500)
    assert bool(res["f64"].converged)
    assert int(res["f64"].outer_iters) == 0
    x64 = np.asarray(res["f64"].x)
    for pol in ("f32_ir", "bf16_ir"):
        r = res[pol]
        assert bool(r.converged) and not bool(r.hit_cap), pol
        assert int(r.outer_iters) >= 1, pol
        diff = float(np.max(np.abs(np.asarray(r.x) - x64)))
        assert diff <= 1e-10, (pol, diff)


@pytest.mark.parametrize("solver", [cg, bicgstab])
@pytest.mark.parametrize("policy", ["f32_ir", "bf16_ir"])
def test_refined_nan_rhs_signature(solver, policy):
    """The NaN health-flag signature survives refinement: the f64 outer
    residual of a NaN rhs kills the outer cond immediately — 0 inner and
    0 outer iterations, converged False AND hit_cap False."""
    pol = get_policy(policy)
    op = lambda v: 2.0 * v
    ops = reference_ops(op, policy=pol, matvec_hi=op)
    b = jnp.ones((2, 32)).at[0, 0].set(jnp.nan)
    res = solver(ops, b, jnp.zeros_like(b), tol=1e-10)
    assert int(res.iters) == 0 and int(res.outer_iters) == 0
    assert not bool(res.converged) and not bool(res.hit_cap)


def test_resolve_backend_fused_min_rows_override(monkeypatch):
    monkeypatch.delenv("REPRO_FUSED_MIN_ROWS", raising=False)
    tpu = dict(on_tpu=True, precision="f32_ir")
    assert resolve_backend("auto", 512, **tpu,
                           fused_min_rows=256) == "fused"
    assert resolve_backend("auto", 512, **tpu,
                           fused_min_rows=1024) == "reference"
    monkeypatch.setenv("REPRO_FUSED_MIN_ROWS", "128")
    assert resolve_backend("auto", 128, **tpu) == "fused"
    assert resolve_backend("auto", 127, **tpu) == "reference"
    # an explicit parameter wins over the environment
    assert resolve_backend("auto", 127, **tpu,
                           fused_min_rows=64) == "fused"


def test_cost_model_precision_bytes():
    cm = CostModel(TPU_V5E, n_dofs=1e6)
    f32 = cm.with_precision("f32_ir")
    bf16 = cm.with_precision("bf16_ir")
    # narrower storage streams fewer bytes/iter, strictly ordered
    assert bf16.solver_bytes() < f32.solver_bytes() < cm.solver_bytes()
    # the f64 policy is the exact pre-policy expression
    assert cm.with_precision("f64").solver_bytes() == cm.solver_bytes()
    # the CPU fallback never runs mixed precision: unchanged
    assert f32.t_solver_cpu(8) == cm.t_solver_cpu(8)
    with pytest.raises(ValueError):
        cm.with_precision("fp8")


def test_plan_cache_precision_key_component():
    mesh = CavityMesh.cube(4, 4)
    cache = PlanCache()
    p64 = cache.plan_for_mesh(mesh, 2, "dia")
    p32 = cache.plan_for_mesh(mesh, 2, "dia", precision="f32_ir")
    p16 = cache.plan_for_mesh(mesh, 2, "dia", precision="bf16_ir")
    assert cache.misses == 3 and cache.hits == 0
    assert cache.plan_for_mesh(mesh, 2, "dia", precision="f32_ir") is p32
    # the default key spells f64 without a precision component (historic)
    assert cache.plan_for_mesh(mesh, 2, "dia", precision="f64") is p64
    assert cache.hits == 2
    # plans are structurally interchangeable; only the cache keys differ
    assert p64.m_coarse == p32.m_coarse == p16.m_coarse
