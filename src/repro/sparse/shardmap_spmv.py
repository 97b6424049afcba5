"""Explicit shard_map DIA SpMV for the beyond-paper full-mesh solve.

GSPMD cannot keep a banded SpMV row-sharded through misaligned static
shifts — it re-gathers the operands inside the CG loop, defeating the
full-mesh layout (measured: EXPERIMENTS.md §Perf C3).  This kernel takes
manual control: rows are sharded over BOTH mesh axes (solve x assemble);
each device holds an ``m_loc``-row slice and exchanges one halo plane with
its linear neighbours via ``collective_permute`` — including across solve-
group boundaries (the fine-linearized order (solve, assemble) makes the
neighbour of the last shard in group k the first shard of group k+1).
The first/last shard mask their outer halo to zero, which matches the
zero interface coefficients of the boundary coarse parts exactly.

**Communication/computation overlap.**  The per-shard apply is split so
the halo ``ppermute``s are issued *first*, the interior contribution —
every row against the locally held vector, which needs no halo — is
computed while the permutes are in flight, and only the boundary-plane
band contributions (first/last ``plane`` rows against the received halo
planes) are added afterwards.  Nothing between the permute and the
boundary add depends on the permuted values, so the XLA scheduler is free
to run the collective concurrently with the interior SpMV — the classic
halo-overlap schedule of GPU-resident PISO solvers (Oliani et al.
arXiv:2403.07882, Tomczak et al. arXiv:1207.1571).

**Local compute.**  The full-mesh solve is f64-only, and the Pallas TPU
kernels take no f64 (an f64 custom call is unimplemented on TPU), so the
per-shard banded apply is the jnp shift loop (``spmv_dia_ref``) on every
backend.

Requires m_loc >= plane (one halo plane per side), i.e. each device holds
at least one z-plane of the fused block — true for all production configs.

:func:`make_jacobi_full_mesh` is the matching preconditioner apply: r/diag
is elementwise, but routing it through the same shard_map keeps the CG
iterates pinned to the (solve, assemble) row layout between SpMVs — GSPMD
would otherwise be free to re-replicate the residual between the two.
:func:`make_fused_ops_full_mesh` bundles everything into the
:class:`~repro.solvers.ops.SolverOps` fused backend: the SpMV pass also
emits the per-shard ``p . Ap`` partial (``psum``'d over both axes), and
the axpy-pair/precondition/reduce half-iteration runs as one shard_map
body with ``psum``'d ``r . z`` / ``r . r`` partials.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.comm import ASSEMBLE_AXIS, SOLVE_AXIS
from repro.kernels.spmv_dia.ref import spmv_dia_ref
from repro.solvers.ops import vdot


def _boundary_add(y, b, down, up, *, offsets, plane, m_loc):
    """Add the halo-dependent band contributions to the boundary planes.

    Row ``i`` takes ``bands[d, i] * x_global[i + off]``; the down halo
    covers global indices ``[-plane, 0)`` (only rows ``i < plane`` with
    ``i + off < 0`` reach it), the up halo ``[m_loc, m_loc + plane)``
    (rows ``i >= m_loc - plane`` with ``i + off >= m_loc``).  Each band's
    valid window is a static slice of a zero-extended halo vector — the
    zero extension supplies the "not from the halo" rows, so no masking.
    """
    dtype = y.dtype
    zeros = jnp.zeros((plane,), dtype)
    down_ext = jnp.concatenate([down, zeros])   # index i+off+plane
    up_ext = jnp.concatenate([zeros, up])       # index (i-(m_loc-plane))+off
    dc = jnp.zeros((plane,), dtype)
    uc = jnp.zeros((plane,), dtype)
    for d, off in enumerate(offsets):
        if off < 0:
            dc = dc + b[d, :plane] * jax.lax.dynamic_slice_in_dim(
                down_ext, plane + off, plane)
        elif off > 0:
            uc = uc + b[d, m_loc - plane:] * jax.lax.dynamic_slice_in_dim(
                up_ext, off, plane)
    y = y.at[:plane].add(dc)
    return y.at[m_loc - plane:].add(uc)


def make_spmv_full_mesh(mesh: Mesh, *, offsets: tuple[int, ...], plane: int,
                        n_coarse: int, alpha: int, m_coarse: int,
                        with_dot: bool = False):
    """Returns A(bands, x) with rows sharded over (solve, assemble).

    bands: (n_c, nb, m_c) global; x: (n_c, m_c) global.  Out like x.
    With ``with_dot=True`` the apply also returns the global ``x . A x``
    (per-shard partial computed in the same pass, ``psum`` over both mesh
    axes) — the fused backend's ``matvec_dot``.
    """
    m_loc = m_coarse // alpha
    assert m_loc >= plane, (m_loc, plane)
    n_shards = n_coarse * alpha
    axes = (SOLVE_AXIS, ASSEMBLE_AXIS)
    fwd = [(i, i + 1) for i in range(n_shards - 1)]   # send up-halo forward
    bwd = [(i + 1, i) for i in range(n_shards - 1)]   # send down-halo back

    out_specs = (P(SOLVE_AXIS, ASSEMBLE_AXIS), P()) if with_dot \
        else P(SOLVE_AXIS, ASSEMBLE_AXIS)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(SOLVE_AXIS, None, ASSEMBLE_AXIS),
                  P(SOLVE_AXIS, ASSEMBLE_AXIS)),
        out_specs=out_specs, check_vma=False)
    def spmv(b_loc, x_loc):
        # b_loc: (1, nb, m_loc); x_loc: (1, m_loc)
        xv = x_loc[0]
        # (1) issue the halo exchanges first ...
        down = jax.lax.ppermute(xv[-plane:], axes, fwd)
        up = jax.lax.ppermute(xv[:plane], axes, bwd)
        lid = jax.lax.axis_index(axes)
        # boundary coarse parts: the outer halo has no neighbour — mask it
        # to zero (the interface coefficients there are zero, so exact)
        down = jnp.where(lid == 0, 0.0, down)
        up = jnp.where(lid == n_shards - 1, 0.0, up)
        # (2) ... interior contribution while the permutes are in flight:
        # zero halos => every row against the locally held vector only
        xp_loc = jnp.concatenate([jnp.zeros((plane,), xv.dtype), xv,
                                  jnp.zeros((plane,), xv.dtype)])
        y = spmv_dia_ref(b_loc[0], xp_loc, offsets=offsets, plane=plane)
        # (3) boundary-plane band contributions from the received halos
        y = _boundary_add(y, b_loc[0], down, up, offsets=offsets,
                          plane=plane, m_loc=m_loc)
        if not with_dot:
            return y[None, :]
        part = vdot(xv, y)
        return y[None, :], jax.lax.psum(part, axes)

    return spmv


def make_jacobi_full_mesh(mesh: Mesh, diag: jax.Array):
    """Jacobi apply M(r) = r / diag on the full-mesh row layout.

    ``diag``: (n_c, m_c) global fused matrix diagonal.  The division is
    purely local per shard (no halo), but running it inside shard_map pins
    the preconditioned residual to P(solve, assemble) so the surrounding
    Krylov iteration never leaves the full-mesh layout.
    """

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(SOLVE_AXIS, ASSEMBLE_AXIS),
                  P(SOLVE_AXIS, ASSEMBLE_AXIS)),
        out_specs=P(SOLVE_AXIS, ASSEMBLE_AXIS), check_vma=False)
    def apply(d_loc, r_loc):
        return r_loc / d_loc

    return lambda r: apply(diag, r)


def make_fused_step_full_mesh(mesh: Mesh, diag: jax.Array):
    """Fused axpy pair + Jacobi inverse + psum'd dots on the full mesh.

    One shard_map body computes ``x' = x + alpha p``, ``r' = r - alpha Ap``,
    ``z = r' / diag`` locally and reduces the ``r'.z`` / ``r'.r'`` partials
    over both mesh axes — the iterates never leave the (solve, assemble)
    layout and the two reductions share one pass over the updated residual.
    """
    axes = (SOLVE_AXIS, ASSEMBLE_AXIS)
    sharded = P(SOLVE_AXIS, ASSEMBLE_AXIS)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(sharded,) * 5 + (P(),),
        out_specs=(sharded, sharded, sharded, P(), P()),
        check_vma=False)
    def step(x_loc, r_loc, p_loc, ap_loc, d_loc, alpha):
        xn = x_loc + alpha * p_loc
        rn = r_loc - alpha * ap_loc
        z = rn / d_loc
        rz = jax.lax.psum(vdot(rn, z), axes)
        rr = jax.lax.psum(vdot(rn, rn), axes)
        return xn, rn, z, rz, rr

    return lambda x, r, p, Ap, alpha: step(x, r, p, Ap, diag, alpha)


def make_fused_ops_full_mesh(mesh: Mesh, bands: jax.Array, diag: jax.Array,
                             *, offsets: tuple[int, ...], plane: int,
                             n_coarse: int, alpha: int, m_coarse: int):
    """The full-mesh fused :class:`~repro.solvers.ops.SolverOps` backend.

    ``bands``/``diag`` are the global fused system in the full-mesh layout
    (constrain them with :func:`repro.core.comm.solve_constraint` first).
    ``matvec_dot`` folds the ``p . Ap`` partial into the overlapped SpMV
    pass; ``fused_step`` is :func:`make_fused_step_full_mesh`; the generic
    ``dots`` stay global vdots (all-reduce over both axes under pjit).
    """
    from repro.solvers.ops import SolverOps, _reference_dots

    kw = dict(offsets=offsets, plane=plane, n_coarse=n_coarse, alpha=alpha,
              m_coarse=m_coarse)
    plain = make_spmv_full_mesh(mesh, **kw)
    fused = make_spmv_full_mesh(mesh, with_dot=True, **kw)
    precond = make_jacobi_full_mesh(mesh, diag)
    step = make_fused_step_full_mesh(mesh, diag)

    return SolverOps(
        matvec=lambda x: plain(bands, x),
        precond=precond,
        matvec_dot=lambda p: fused(bands, p),
        fused_step=step,
        dots=_reference_dots,
        backend="fused",
    )
