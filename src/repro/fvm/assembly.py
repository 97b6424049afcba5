"""FVM assembly for icoFOAM on the distributed slab-decomposed box mesh.

Assembles, on the **fine (CPU/assembly) partition**, the LDU coefficients of

* the momentum predictor  ``ddt(U) + div(phi, U) - nu*laplacian(U) = -grad(p)``
  (upwind convection, central diffusion — the same matrix for all three
  velocity components, per OpenFOAM), and
* the segregated pressure equation ``laplacian(rAU, p) = div(phiHbyA)``.

All arrays are stacked over the fine part axis (P, ...) — the SPMD layout.

Boundary conditions come from a :class:`~repro.fvm.cases.FlowCase` (one
:class:`~repro.fvm.cases.PatchBC` per box face).  The default is the
paper's lid-driven cavity — no-slip walls, moving lid (1,0,0) at z=max,
zeroGradient pressure with a reference cell (OpenFOAM ``setReference``) —
whose boundary faces all have zero normal velocity, so its boundary
convective fluxes vanish identically.  Inlet/outlet cases additionally
carry a **boundary-flux plane pair** ``phi_b`` of shape ``(P, 2, B)``
(slot ``DOWN`` = the ``z0`` face, slot ``UP`` = ``z1`` — the same plane
layout as the interface fluxes): inlets contribute a fixed Dirichlet flux
and a convective inflow source, outlets drop the boundary diffusion term
(zero-gradient U), pin ``p = 0`` over the half cell (no reference cell
needed), and get their flux corrected conservatively alongside the
internal faces.  Boundary diffusion of Dirichlet patches uses the
half-cell distance h/2.
"""
from __future__ import annotations

import copy
import dataclasses
from functools import cached_property

import jax
import jax.numpy as jnp
import numpy as np

from repro.fvm.cases import (FlowCase, INLET, MOVING_WALL, OUTLET, PatchBC,
                             get_case)
from repro.core.rows import RowSet, add_rows, take_rows
from repro.fvm.mesh import CavityMesh, DOWN, UP
from repro.sparse.distributed import halo_exchange

__all__ = ["CavityAssembly", "MomentumSystem", "PressureSystem"]


@dataclasses.dataclass
class MomentumSystem:
    """LDU coefficients (fine partition) + per-component RHS."""

    diag: jax.Array    # (P, m)
    upper: jax.Array   # (P, F)  a(owner, neigh)
    lower: jax.Array   # (P, F)  a(neigh, owner)
    iface: jax.Array   # (P, 2, B) interface coefficients (masked at z-bounds)
    source: jax.Array  # (P, m, 3)


@dataclasses.dataclass
class PressureSystem:
    diag: jax.Array    # (P, m)
    upper: jax.Array   # (P, F)
    lower: jax.Array   # (P, F)
    iface: jax.Array   # (P, 2, B)
    source: jax.Array  # (P, m)
    g_int: jax.Array   # (P, F) face conductances (for flux correction)
    g_if: jax.Array    # (P, 2, B)
    g_b: jax.Array     # (P, 2, B) outlet (Dirichlet-p) boundary conductances


# pytree registration lets the systems cross jit boundaries — the
# StepProgram's instrumented executor (fvm/step_program) passes them
# between phase-jitted functions instead of fusing the whole timestep
# into one program.
for _cls in (MomentumSystem, PressureSystem):
    jax.tree_util.register_dataclass(
        _cls, data_fields=[f.name for f in dataclasses.fields(_cls)],
        meta_fields=[])


def _patch_role(normal) -> str:
    """Geometric role of a patch from its outward normal (cases.ROLES)."""
    axis = int(np.argmax(np.abs(normal)))
    return "xyz"[axis] + ("1" if normal[axis] > 0 else "0")


class CavityAssembly:
    """Precomputed static addressing + assembly routines for one mesh.

    ``case`` binds a :class:`~repro.fvm.cases.FlowCase` BC set (name,
    instance, or ``None`` for the classic cavity built from
    ``lid_speed``); assembly masks, Dirichlet velocities, boundary-flux
    slots and the pressure reference policy all derive from it.
    """

    def __init__(self, mesh: CavityMesh, *, nu: float = 0.01,
                 lid_speed: float = 1.0, dtype=jnp.float64,
                 case: FlowCase | str | None = None):
        self.mesh = mesh
        self.nu = nu
        self.lid_speed = lid_speed
        self.dtype = dtype
        if case is None:
            # the historical default: the cavity with its lid at lid_speed
            case = get_case("cavity", u_ref=lid_speed)
            case = dataclasses.replace(
                case, bcs={"z1": PatchBC(MOVING_WALL,
                                         U=(lid_speed, 0.0, 0.0))})
        self.case = get_case(case)
        P = mesh.n_parts
        m = mesh.n_cells
        self.owner = RowSet.build(mesh.owner, m)
        self.neigh = RowSet.build(mesh.neigh, m)
        self.face_axis = jnp.asarray(mesh.face_axis, jnp.int32)
        # (DOWN, UP) interface row sets
        self.if_rows = tuple(RowSet.build(s.rows, m) for s in mesh.ifaces)
        # (P, 2) presence mask for interfaces, broadcast over faces
        self.if_mask = jnp.asarray(mesh.iface_mask(), dtype)[:, :, None]
        # boundary patches: per-patch BC kind + Dirichlet velocity, bound
        # from the case by geometric role.  patch_Ub entries are (3,)
        # uniform values or (n_bf, 3) per-face values (profiled inlets).
        self.patch_rows = [RowSet.build(p.rows, m) for p in mesh.patches]
        self.patch_mask = jnp.asarray(mesh.patch_mask(), dtype)  # (P, n_patches)
        self.patch_kind = [self.case.bc(_patch_role(p.normal)).kind
                           for p in mesh.patches]
        self.patch_Ub = [self._patch_Ub(p) for p in mesh.patches]
        self.V = mesh.volume
        self.A = mesh.area
        self.h = mesh.h
        self.plane = mesh.plane
        self.n_parts = P
        self.m = m
        # outward z-normal per patch, for dynamic part-activity masks: the
        # +z patch rides on the last active part, the -z patch on part 0;
        # everything else is on every active part
        self._patch_nz = [p.normal[2] for p in mesh.patches]
        # z-plane patches own the (P, 2, B) boundary-flux slots: slot DOWN
        # is the z0 face, slot UP the z1 face (rows match if_rows order)
        self._z_patch = {DOWN if nz < 0 else UP: pi
                         for pi, nz in enumerate(self._patch_nz) if nz != 0}
        self._needs_ref = self.case.needs_ref

    def _patch_Ub(self, patch) -> jax.Array:
        """Dirichlet boundary velocity of one patch: (3,) uniform, or
        (n_bf, 3) per-face for a profiled inlet (outlets get zeros —
        their velocity is zero-gradient, never sourced)."""
        bc = self.case.bc(_patch_role(patch.normal))
        U = jnp.asarray(bc.U if bc.kind != OUTLET else (0.0, 0.0, 0.0),
                        self.dtype)
        if bc.kind == INLET and bc.profile == "upper_half":
            # plane rows are _plane_cells order: t -> (i = t % nx,
            # j = t // nx); the inlet spans the j >= ny/2 half
            j = np.arange(len(patch.rows)) // self.mesh.nx
            prof = jnp.asarray(j >= self.mesh.ny // 2, self.dtype)
            return prof[:, None] * U[None, :]
        return U

    def _z_Ub_face(self, slot: int) -> jax.Array:
        """(B, 3) Dirichlet velocity over a z-plane slot (zeros for
        outlet: inflow across an outlet convects nothing)."""
        Ub = self.patch_Ub[self._z_patch[slot]]
        return jnp.broadcast_to(jnp.atleast_2d(Ub),
                                (self.plane, 3)).astype(self.dtype)

    # ------------------------------------------------------------------
    # part-activity masks (size-class padding support)
    # ------------------------------------------------------------------
    def dynamic_masks(self, n_active) -> tuple[jax.Array, jax.Array]:
        """``(if_mask, patch_mask)`` as traced functions of ``n_active``.

        ``n_active`` is the number of *real* leading parts; parts at and
        beyond it are size-class zero padding (ghost slabs) with no
        interfaces and no boundary patches.  The lid patch rides on the
        last active part and the bottom wall on part 0, matching the
        static masks of a :class:`~repro.fvm.mesh.PaddedCavityMesh`.
        Making the masks a function of a traced scalar is what lets one
        compiled (and vmapped) program serve sessions of *different* real
        sizes inside one padded size class.
        """
        ids = jnp.arange(self.n_parts)
        act = ids < n_active
        down = act & (ids >= 1)
        up = ids < (n_active - 1)
        if_mask = jnp.stack([down, up], axis=1).astype(self.dtype)[:, :, None]
        cols = []
        for nz in self._patch_nz:
            if nz > 0:        # lid: last active part
                cols.append(act & (ids == n_active - 1))
            elif nz < 0:      # bottom wall: part 0
                cols.append(act & (ids == 0))
            else:             # side walls: every active part
                cols.append(act)
        patch_mask = jnp.stack(cols, axis=1).astype(self.dtype)
        return if_mask, patch_mask

    def with_masks(self, if_mask: jax.Array,
                   patch_mask: jax.Array) -> "CavityAssembly":
        """A shallow view of this assembly with the activity masks swapped
        (static addressing shared).  Used by the padded StepProgram to
        bind per-session traced masks without rebuilding the assembly."""
        a = copy.copy(self)
        a.if_mask = if_mask
        a.patch_mask = patch_mask
        return a

    # ------------------------------------------------------------------
    # face interpolation / fluxes
    # ------------------------------------------------------------------
    def face_flux(self, U: jax.Array) -> tuple[jax.Array, jax.Array]:
        """phi (P,F) internal fluxes and phi_if (P,2,B) interface fluxes.

        phi_f = 0.5*(U_o + U_n)[axis] * A, oriented owner→neigh.  Interface
        fluxes are *outward* of the owning part (down: -z, up: +z).
        """
        Uf = 0.5 * (take_rows(U, self.owner.idx)
                    + take_rows(U, self.neigh.idx))
        ax = self.face_axis
        comp = jnp.where(ax == 0, Uf[..., 0],
                         jnp.where(ax == 1, Uf[..., 1], Uf[..., 2]))
        phi = comp * self.A
        # interface: halo of w-velocity planes
        w = U[..., 2]
        down, up = halo_exchange(w, self.plane)  # remote plane values
        w_down_local = take_rows(w, self.if_rows[DOWN].idx)
        w_up_local = take_rows(w, self.if_rows[UP].idx)
        phi_down = -self.A * 0.5 * (w_down_local + down)   # outward -z
        phi_up = +self.A * 0.5 * (w_up_local + up)         # outward +z
        phi_if = jnp.stack([phi_down, phi_up], axis=1) * self.if_mask
        return phi, phi_if

    def boundary_flux(self, U: jax.Array) -> jax.Array:
        """(P, 2, B) outward boundary fluxes of the z-plane patches.

        Dirichlet patches (walls, lid, inlets) contribute their *fixed*
        flux ``U_b . n A`` — independent of ``U``, zero for every wall —
        while an outlet's zero-gradient flux extrapolates the owner-cell
        velocity.  x/y wall patches never carry a normal flux (the case
        registry restricts inlet/outlet to z-faces), so the plane pair
        covers every nonzero boundary flux.  Masked by the active
        ``patch_mask`` view, so padded ghost slabs stay flux-free.
        """
        P = U.shape[0]
        phi_b = jnp.zeros((P, 2, self.plane), self.dtype)
        for slot, pi in self._z_patch.items():
            rows = self.patch_rows[pi]
            mask = self.patch_mask[:, pi]
            nz = self._patch_nz[pi]
            if self.patch_kind[pi] == OUTLET:
                f = take_rows(U[..., 2], rows.idx) * (nz * self.A)
            else:
                w = jnp.atleast_2d(self.patch_Ub[pi])[:, 2]  # (1,) or (B,)
                f = jnp.broadcast_to(w * (nz * self.A), (P, self.plane))
            phi_b = phi_b.at[:, slot].set(f * mask[:, None])
        return phi_b.astype(self.dtype)

    # ------------------------------------------------------------------
    # Gauss gradient with zero-gradient boundary pressure
    # ------------------------------------------------------------------
    def grad(self, p: jax.Array) -> jax.Array:
        """(P, m, 3) Gauss gradient of a cell scalar field."""
        P, m = p.shape
        g = jnp.zeros((P, m, 3), self.dtype)
        pf = 0.5 * (take_rows(p, self.owner.idx)
                    + take_rows(p, self.neigh.idx))
        sf = jax.nn.one_hot(self.face_axis, 3, dtype=self.dtype) * self.A  # (F,3)
        contrib = pf[:, :, None] * sf[None, :, :]
        g = add_rows(g, self.owner, contrib)
        g = add_rows(g, self.neigh, -contrib)
        # interfaces: S = ±A e_z outward
        down, up = halo_exchange(p, self.plane)
        pf_down = (0.5 * (take_rows(p, self.if_rows[DOWN].idx) + down)
                   * self.if_mask[:, DOWN])
        pf_up = (0.5 * (take_rows(p, self.if_rows[UP].idx) + up)
                 * self.if_mask[:, UP])
        gz = add_rows(g[..., 2], self.if_rows[DOWN], -self.A * pf_down)
        gz = add_rows(gz, self.if_rows[UP], self.A * pf_up)
        g = g.at[..., 2].set(gz)
        # boundaries: zero-gradient ⇒ p_b = p_owner, S = A n_outward;
        # outlets pin p_b = 0 (Dirichlet), so their face term vanishes
        for rows, mask, kind, patch in zip(self.patch_rows,
                                           self.patch_mask.T,
                                           self.patch_kind,
                                           self.mesh.patches):
            if kind == OUTLET:
                continue
            n = jnp.asarray(patch.normal, self.dtype)
            pb = take_rows(p, rows.idx) * mask[:, None]
            g = add_rows(g, rows, pb[:, :, None] * (self.A * n)[None, None, :])
        return g / self.V

    def divergence(self, phi: jax.Array, phi_if: jax.Array,
                   phi_b: jax.Array | None = None) -> jax.Array:
        """(P, m) cell divergence of face fluxes (outward-positive);
        ``phi_b`` adds the z-plane boundary fluxes (inlet/outlet cases)."""
        P = phi.shape[0]
        d = jnp.zeros((P, self.m), self.dtype)
        d = add_rows(d, self.owner, phi)
        d = add_rows(d, self.neigh, -phi)
        d = add_rows(d, self.if_rows[DOWN], phi_if[:, DOWN])
        d = add_rows(d, self.if_rows[UP], phi_if[:, UP])
        if phi_b is not None:
            d = add_rows(d, self.if_rows[DOWN], phi_b[:, DOWN])
            d = add_rows(d, self.if_rows[UP], phi_b[:, UP])
        return d

    # ------------------------------------------------------------------
    # momentum predictor
    # ------------------------------------------------------------------
    def assemble_momentum(self, U_old: jax.Array, phi: jax.Array,
                          phi_if: jax.Array, p: jax.Array,
                          dt: float,
                          phi_b: jax.Array | None = None,
                          gradp: jax.Array | None = None) -> MomentumSystem:
        """``gradp`` short-circuits the pressure-gradient source: when the
        caller already holds ``grad(p)`` (the pipelined executor carries it
        across the step boundary in its ring), it is consumed directly and
        ``p`` is never touched — pass ``p=None`` in that case."""
        P, m = U_old.shape[:2]
        F = phi.shape[1]
        diag = jnp.full((P, m), self.V / dt, self.dtype)
        source = (self.V / dt) * U_old
        upper = jnp.zeros((P, F), self.dtype)
        lower = jnp.zeros((P, F), self.dtype)
        iface = jnp.zeros_like(phi_if)

        # convection, upwind
        diag = add_rows(diag, self.owner, jnp.maximum(phi, 0.0))
        upper = upper + jnp.minimum(phi, 0.0)
        diag = add_rows(diag, self.neigh, jnp.maximum(-phi, 0.0))
        lower = lower + jnp.minimum(-phi, 0.0)
        diag = add_rows(diag, self.if_rows[DOWN],
                        jnp.maximum(phi_if[:, DOWN], 0.0))
        diag = add_rows(diag, self.if_rows[UP],
                        jnp.maximum(phi_if[:, UP], 0.0))
        iface = iface + jnp.minimum(phi_if, 0.0)

        # boundary convection (z-plane patches, upwind): outflow convects
        # the owner value (diagonal), inflow convects the Dirichlet
        # boundary velocity (source).  Identically zero for the cavity
        # (every wall flux vanishes).
        if phi_b is not None:
            for slot in (DOWN, UP):
                rows = self.if_rows[slot]
                diag = add_rows(diag, rows, jnp.maximum(phi_b[:, slot], 0.0))
                Ub = self._z_Ub_face(slot)
                source = add_rows(
                    source, rows,
                    (-jnp.minimum(phi_b[:, slot], 0.0))[..., None]
                    * Ub[None, :, :])

        # diffusion, central
        g = self.nu * self.A / self.h
        diag = add_rows(diag, self.owner, g)
        diag = add_rows(diag, self.neigh, g)
        upper = upper - g
        lower = lower - g
        diag = add_rows(diag, self.if_rows[DOWN], g * self.if_mask[:, DOWN])
        diag = add_rows(diag, self.if_rows[UP], g * self.if_mask[:, UP])
        iface = iface - g * self.if_mask

        # boundary diffusion (Dirichlet walls/lid/inlets, half-cell
        # distance); outlets are zero-gradient — no boundary term
        gb = self.nu * self.A / (0.5 * self.h)
        for rows, mask, Ub, kind in zip(self.patch_rows, self.patch_mask.T,
                                        self.patch_Ub, self.patch_kind):
            if kind == OUTLET:
                continue
            diag = add_rows(diag, rows, gb * mask[:, None])
            source = add_rows(
                source, rows,
                gb * mask[:, None, None] * jnp.atleast_2d(Ub)[None, ...])

        # pressure gradient source
        source = source - self.V * (self.grad(p) if gradp is None else gradp)
        return MomentumSystem(diag, upper, lower, iface, source)

    def offdiag_apply(self, sys, x: jax.Array) -> jax.Array:
        """y = (A - diag) x on the fine partition (for OpenFOAM's H())."""
        y = jnp.zeros_like(x)
        y = add_rows(y, self.owner, sys.upper * take_rows(x, self.neigh.idx))
        y = add_rows(y, self.neigh, sys.lower * take_rows(x, self.owner.idx))
        down, up = halo_exchange(x, self.plane)
        y = add_rows(y, self.if_rows[DOWN], sys.iface[:, DOWN] * down)
        y = add_rows(y, self.if_rows[UP], sys.iface[:, UP] * up)
        return y

    # ------------------------------------------------------------------
    # PISO pressure equation
    # ------------------------------------------------------------------
    def assemble_pressure_matrix(self, rAU: jax.Array,
                                 ref_boost: float = 1.0) -> PressureSystem:
        """The corrector-invariant half of :meth:`assemble_pressure`.

        Every matrix coefficient of the pressure equation — conductances,
        diagonal, off-diagonals, outlet boundary conductances, reference
        boost — depends only on ``rAU = V / diag(momentum)``, which is fixed
        for the whole PISO step.  Splitting it out lets the pipelined
        executor build the matrix once per step (and plan its Jacobi bands
        once) while each corrector re-assembles only the divergence source.
        Returns a :class:`PressureSystem` with a **zero** source.
        """
        P, m = rAU.shape
        rAUf = 0.5 * (take_rows(rAU, self.owner.idx)
                      + take_rows(rAU, self.neigh.idx))
        g_int = rAUf * self.A / self.h
        down, up = halo_exchange(rAU, self.plane)
        g_down = (0.5 * (take_rows(rAU, self.if_rows[DOWN].idx) + down)
                  * self.A / self.h)
        g_up = (0.5 * (take_rows(rAU, self.if_rows[UP].idx) + up)
                * self.A / self.h)
        g_if = jnp.stack([g_down, g_up], axis=1) * self.if_mask

        diag = jnp.zeros((P, m), self.dtype)
        diag = add_rows(diag, self.owner, g_int)
        diag = add_rows(diag, self.neigh, g_int)
        diag = add_rows(diag, self.if_rows[DOWN], g_if[:, DOWN])
        diag = add_rows(diag, self.if_rows[UP], g_if[:, UP])
        upper = -g_int
        lower = -g_int
        iface = -g_if

        # outlet Dirichlet-p conductances, (P, 2, B) plane pair
        g_b = jnp.zeros((P, 2, self.plane), self.dtype)
        for slot, pi in self._z_patch.items():
            if self.patch_kind[pi] != OUTLET:
                continue
            rows = self.if_rows[slot]
            gb = take_rows(rAU, rows.idx) * (self.A / (0.5 * self.h))
            g_b = g_b.at[:, slot].set(gb * self.patch_mask[:, pi][:, None])
            diag = add_rows(diag, rows, g_b[:, slot])

        if self._needs_ref:
            # reference cell: diag *= (1 + boost) at global cell 0
            # (OpenFOAM-like); redundant (and skipped) with an outlet
            boost = jnp.zeros((P, m), self.dtype).at[0, 0].set(ref_boost)
            diag = diag * (1.0 + boost)
        source = jnp.zeros((P, m), self.dtype)
        return PressureSystem(diag, upper, lower, iface, source,
                              g_int, g_if, g_b)

    def assemble_pressure(self, rAU: jax.Array, phiHbyA: jax.Array,
                          phiHbyA_if: jax.Array,
                          phiHbyA_b: jax.Array | None = None,
                          ref_boost: float = 1.0) -> PressureSystem:
        """-laplacian(rAU, p) = -div(phiHbyA), SPD form for CG.

        Face conductance ``g_f = rAU_f * A / h`` with linear interpolation of
        rAU.  Outlet patches carry a Dirichlet p = 0 at the half-cell
        boundary distance (``g_b = rAU * A / (h/2)`` added to the diagonal
        only — the fixed boundary value contributes nothing to the source),
        which pins the pressure level.  Cases without an outlet are
        all-Neumann; there, ``setReference``: the global reference cell
        (part 0, cell 0) gets its diagonal boosted (refValue = 0),
        removing the nullspace.

        Delegates the matrix half to :meth:`assemble_pressure_matrix` and
        fills in the divergence source — bitwise-identical to the previous
        monolithic assembly (the matrix block never reads the source).
        """
        sys = self.assemble_pressure_matrix(rAU, ref_boost=ref_boost)
        return dataclasses.replace(
            sys, source=-self.divergence(phiHbyA, phiHbyA_if, phiHbyA_b))

    def correct_flux(self, sysP: PressureSystem, phiHbyA, phiHbyA_if, p):
        """phi = phiHbyA - g_f (p_n - p_o); conservative by construction."""
        dp = take_rows(p, self.neigh.idx) - take_rows(p, self.owner.idx)
        phi = phiHbyA - sysP.g_int * dp
        down, up = halo_exchange(p, self.plane)
        dp_down = down - take_rows(p, self.if_rows[DOWN].idx)  # outward (-z)
        dp_up = up - take_rows(p, self.if_rows[UP].idx)
        phi_if = phiHbyA_if - jnp.stack(
            [sysP.g_if[:, DOWN] * dp_down, sysP.g_if[:, UP] * dp_up], axis=1)
        return phi, phi_if * self.if_mask

    def correct_boundary_flux(self, sysP: PressureSystem, phiHbyA_b, p):
        """phi_b = phiHbyA_b - g_b (p_b - p_o) with outlet p_b = 0.

        ``g_b`` is zero except on outlet planes, so inlet/wall boundary
        fluxes pass through unchanged; outlet fluxes pick up the Dirichlet
        correction that makes the corrected field conservative cell-wise
        (same ``g_b`` as the matrix diagonal, mirroring OpenFOAM's
        ``fixedValue`` pressure-flux correction).
        """
        corr = jnp.stack(
            [sysP.g_b[:, DOWN] * take_rows(p, self.if_rows[DOWN].idx),
             sysP.g_b[:, UP] * take_rows(p, self.if_rows[UP].idx)], axis=1)
        return phiHbyA_b + corr
