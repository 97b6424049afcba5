"""Plain reference of one PISO timestep on the uniform box mesh.

The same semantics as the program under test, written independently of it:
the whole domain as dense ``(nz, ny, nx)`` arrays, no partition, no
repartition, no DIA bands and no kernels.  Face fluxes are held per axis:
``fx`` ``(nz, ny, nx-1)`` through the faces between x-neighbours (positive
towards +x), ``fy`` ``(nz, ny-1, nx)``, ``fz`` ``(nz-1, ny, nx)``, and ``fb``
``(2, ny, nx)``, the outward fluxes through the z0 and z1 boundary faces.
The x and y boundary faces carry no flux (only z faces may be inlets or
outlets).

One step (icoFoam's PISO with the discretisation of the box mesh):

1. momentum ``V/dt (U - U0) + sum_f F_f U_f - nu sum_f A/d (U_nb - U) =
   -V grad p``, upwind convection on the old fluxes, central diffusion
   (``d = h`` inside, ``h/2`` to a Dirichlet boundary face), solved per
   component;
2. per corrector: ``rAU = V / a_P``, ``HbyA = (b - sum_nb a_nb U_nb) / a_P``
   with ``b`` the whole momentum source (the ``-V grad p`` term included),
   fluxes ``F_H`` of the face-averaged ``HbyA``, the pressure equation
   ``sum_f g_f (p_P - p_nb) = -div F_H`` with ``g_f = rAU_f A / h`` (and
   ``rAU_P A / (h/2)`` to a ``p = 0`` outlet; without an outlet the
   diagonal of cell (0, 0, 0) is doubled), then ``F = F_H - g_f (p_nb -
   p_P)`` and ``U = HbyA - rAU grad p``.

``grad`` is the Gauss gradient with face averages inside, the cell value at
zero-gradient boundary faces and 0 at outlets.  The linear systems are
solved far below the program's tolerances: momentum by Jacobi iteration
(the matrix is strictly diagonally dominant through ``V/dt``), pressure by
Jacobi-preconditioned conjugate gradients.  Every array takes ``dtype``, so
the same code in a lower precision is the control of the comparison.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

ROLES = ("x0", "x1", "y0", "y1", "z0", "z1")
DIRICHLET = ("wall", "moving_wall", "inlet")
# array axis of each coordinate in a (nz, ny, nx) field
AXIS = {"x": 2, "y": 1, "z": 0}
FIELDS = ("U", "p", "fx", "fy", "fz", "fb")


@dataclasses.dataclass(frozen=True)
class Box:
    """The box case: ``n`` cells per edge of length ``length``, viscosity
    ``nu``, timestep ``dt`` and one boundary condition per face role."""

    n: int
    length: float
    nu: float
    dt: float
    bcs: tuple  # ((role, kind, (Ux, Uy, Uz), profile), ...) for all roles

    @property
    def h(self) -> float:
        return self.length / self.n

    def bc(self, role: str):
        for r, kind, u, profile in self.bcs:
            if r == role:
                return kind, u, profile
        raise KeyError(role)

    @property
    def has_outlet(self) -> bool:
        return any(kind == "outlet" for _, kind, _, _ in self.bcs)


def box_from(config: dict, co: float) -> Box:
    """The :class:`Box` of a configuration file and a Courant number."""
    n, length = int(config["n"]), float(config["length"])
    nu = config["u_ref"] * length / config["reynolds"]
    given = config.get("boundary", {})
    bcs = []
    for role in ROLES:
        spec = given.get(role, {"kind": "wall"})
        kind = spec["kind"]
        if kind not in DIRICHLET + ("outlet",):
            raise ValueError(f"unknown boundary kind {kind!r} on {role}")
        if kind in ("inlet", "outlet") and role[0] != "z":
            raise ValueError(f"{kind} on {role}: only z faces may carry flux")
        u = tuple(float(c) for c in spec.get("U", (0.0, 0.0, 0.0)))
        bcs.append((role, kind, u if kind != "outlet" else (0.0, 0.0, 0.0),
                    spec.get("profile", "uniform")))
    return Box(n=n, length=length, nu=nu, dt=co * length / n / config["u_ref"],
               bcs=tuple(bcs))


# ---------------------------------------------------------------------------
# dense stencil helpers
# ---------------------------------------------------------------------------

def _lo(q, ax):
    """Cells 0..n-2 along ``ax`` (the low side of each interior face)."""
    return jax.lax.slice_in_dim(q, 0, q.shape[ax] - 1, axis=ax)


def _hi(q, ax):
    """Cells 1..n-1 along ``ax`` (the high side of each interior face)."""
    return jax.lax.slice_in_dim(q, 1, q.shape[ax], axis=ax)


def _pad(q, ax, before, after):
    widths = [(0, 0)] * q.ndim
    widths[ax] = (before, after)
    return jnp.pad(q, widths)


def _face_avg(q, ax):
    return 0.5 * (_lo(q, ax) + _hi(q, ax))


def _face(role: str) -> tuple:
    """Index of the boundary cells of ``role`` in a (nz, ny, nx) field."""
    idx = [slice(None)] * 3
    idx[AXIS[role[0]]] = 0 if role[1] == "0" else -1
    return tuple(idx)


def _boundary_u(box: Box, role: str, shape, dtype):
    """Dirichlet velocity over the boundary cells of ``role``, shaped as
    that face plus ``(3,)``: uniform, or over the ``y >= ny/2`` half of a z
    face for an ``upper_half`` inlet."""
    _, u, profile = box.bc(role)
    plane = [n for ax, n in enumerate(shape) if ax != AXIS[role[0]]]
    mask = np.ones(plane)
    if profile == "upper_half":
        mask[np.arange(plane[0]) < shape[1] // 2] = 0.0
    return jnp.asarray(mask[..., None] * np.asarray(u), dtype)


def face_flux(box: Box, V):
    """Interior fluxes of the face-averaged velocity field ``V``."""
    A = box.h ** 2
    return tuple(_face_avg(V[..., c], AXIS[a]) * A
                 for c, a in enumerate("xyz"))


def boundary_flux(box: Box, V):
    """``(2, ny, nx)`` outward z0/z1 fluxes: fixed ``U_b . n A`` on
    Dirichlet faces, the owner cell's ``w`` on an outlet."""
    A = box.h ** 2
    out = []
    for role, k, sign in (("z0", 0, -1.0), ("z1", -1, 1.0)):
        if box.bc(role)[0] == "outlet":
            out.append(sign * A * V[k, :, :, 2])
        else:
            ub = _boundary_u(box, role, V.shape[:3], V.dtype)[..., 2]
            out.append(sign * A * ub)
    return jnp.stack(out)


def grad(box: Box, p):
    """Gauss gradient ``(nz, ny, nx, 3)``: face averages inside, the cell
    value at zero-gradient boundary faces, 0 at outlets."""
    A, V = box.h ** 2, box.h ** 3
    comps = []
    for a in "xyz":
        ax = AXIS[a]
        inner = _face_avg(p, ax)
        low = jax.lax.slice_in_dim(p, 0, 1, axis=ax)
        high = jax.lax.slice_in_dim(p, p.shape[ax] - 1, p.shape[ax], axis=ax)
        if box.bc(a + "0")[0] == "outlet":
            low = jnp.zeros_like(low)
        if box.bc(a + "1")[0] == "outlet":
            high = jnp.zeros_like(high)
        faces = jnp.concatenate([low, inner, high], axis=ax)
        comps.append((_hi(faces, ax) - _lo(faces, ax)) * (A / V))
    return jnp.stack(comps, axis=-1)


def divergence(fluxes, fb):
    """Net outward flux of every cell."""
    fx, fy, fz = fluxes
    d = (_pad(fx, 2, 0, 1) - _pad(fx, 2, 1, 0)
         + _pad(fy, 1, 0, 1) - _pad(fy, 1, 1, 0)
         + _pad(fz, 0, 0, 1) - _pad(fz, 0, 1, 0))
    d = d.at[0].add(fb[0])
    return d.at[-1].add(fb[1])


@dataclasses.dataclass
class Stencil:
    """A 7-point operator: ``diag`` and the coefficient of each neighbour,
    ``lo[a]`` towards the lower and ``hi[a]`` the higher cell along axis
    ``a`` (zero where there is none)."""

    diag: jax.Array
    lo: tuple
    hi: tuple

    def offdiag(self, x):
        """``sum_nb a_nb x_nb`` for a cell field (a trailing component axis
        broadcasts)."""
        y = jnp.zeros_like(x)
        for ax in (0, 1, 2):
            lo, hi = self.lo[ax], self.hi[ax]
            if x.ndim == 4:
                lo, hi = lo[..., None], hi[..., None]
            y = y + lo * _pad(_lo(x, ax), ax, 1, 0)
            y = y + hi * _pad(_hi(x, ax), ax, 0, 1)
        return y

    def apply(self, x):
        d = self.diag[..., None] if x.ndim == 4 else self.diag
        return d * x + self.offdiag(x)


def momentum(box: Box, U0, p, fluxes, fb):
    """The momentum stencil and its ``(nz, ny, nx, 3)`` source."""
    A, V, h = box.h ** 2, box.h ** 3, box.h
    dtype = U0.dtype
    shape = U0.shape[:3]
    g = box.nu * A / h
    diag = jnp.full(shape, V / box.dt, dtype)
    src = (V / box.dt) * U0 - V * grad(box, p)
    lo, hi = [None] * 3, [None] * 3
    for a, f in zip("xyz", fluxes):
        ax = AXIS[a]
        # upwind convection and central diffusion through interior faces
        diag = (diag + _pad(jnp.maximum(f, 0.0) + g, ax, 0, 1)
                + _pad(jnp.maximum(-f, 0.0) + g, ax, 1, 0))
        hi[ax] = _pad(jnp.minimum(f, 0.0) - g, ax, 0, 1)
        lo[ax] = _pad(jnp.minimum(-f, 0.0) - g, ax, 1, 0)
    # convection through the z boundary faces: outflow on the diagonal,
    # inflow of the Dirichlet velocity into the source
    for k, role in ((0, "z0"), (-1, "z1")):
        ub = _boundary_u(box, role, shape, dtype)
        diag = diag.at[k].add(jnp.maximum(fb[k], 0.0))
        src = src.at[k].add(-jnp.minimum(fb[k], 0.0)[..., None] * ub)
    # diffusion to Dirichlet boundary faces (outlets are zero-gradient)
    gb = box.nu * A / (0.5 * h)
    for role in ROLES:
        kind = box.bc(role)[0]
        if kind == "outlet":
            continue
        face = _face(role)
        diag = diag.at[face].add(gb)
        src = src.at[face].add(gb * _boundary_u(box, role, shape, dtype))
    return Stencil(diag, tuple(lo), tuple(hi)), src


def pressure(box: Box, rAU):
    """The pressure stencil, its face conductances and the outlet
    conductances ``(2, ny, nx)``."""
    A, h = box.h ** 2, box.h
    diag = jnp.zeros_like(rAU)
    lo, hi, cond = [None] * 3, [None] * 3, [None] * 3
    for a in "xyz":
        ax = AXIS[a]
        gf = _face_avg(rAU, ax) * (A / h)
        cond[ax] = gf
        diag = diag + _pad(gf, ax, 0, 1) + _pad(gf, ax, 1, 0)
        hi[ax] = _pad(-gf, ax, 0, 1)
        lo[ax] = _pad(-gf, ax, 1, 0)
    gb = []
    for k, role in ((0, "z0"), (-1, "z1")):
        if box.bc(role)[0] == "outlet":
            gb.append(rAU[k] * (A / (0.5 * h)))
            diag = diag.at[k].add(gb[-1])
        else:
            gb.append(jnp.zeros_like(rAU[k]))
    if not box.has_outlet:
        diag = diag.at[0, 0, 0].multiply(2.0)
    return Stencil(diag, tuple(lo), tuple(hi)), tuple(cond), jnp.stack(gb)


# ---------------------------------------------------------------------------
# solvers
# ---------------------------------------------------------------------------

def _dot(a, b):
    return jnp.sum(a * b)


def jacobi(op: Stencil, b, x0, tol, maxiter):
    """Jacobi iteration on ``op x = b`` (all components at once) until every
    component's residual is at most ``tol`` times its right-hand side."""
    d = op.diag[..., None]
    bb = jnp.sum(b * b, axis=(0, 1, 2))

    def resid(x):
        r = b - op.apply(x)
        return r, jnp.max(jnp.sum(r * r, axis=(0, 1, 2)) / bb)

    def cond(s):
        return (s[2] > tol * tol) & (s[3] < maxiter)

    def body(s):
        x, r, _, k = s
        x = x + r / d
        r, q = resid(x)
        return x, r, q, k + 1

    r0, q0 = resid(x0)
    x, _, q, k = jax.lax.while_loop(cond, body, (x0, r0, q0, 0))
    return x, jnp.sqrt(q), k


def pcg(op: Stencil, b, x0, tol, maxiter):
    """Jacobi-preconditioned CG on the symmetric ``op`` until ``|r| <= tol
    |b|``."""
    inv = 1.0 / op.diag
    thr = tol * tol * _dot(b, b)
    r = b - op.apply(x0)
    z = r * inv

    def cond(s):
        return (s[4] > thr) & (s[5] < maxiter)

    def body(s):
        x, r, p, rz, _, k = s
        Ap = op.apply(p)
        alpha = rz / _dot(p, Ap)
        x = x + alpha * p
        r = r - alpha * Ap
        z = r * inv
        rz_new = _dot(r, z)
        return x, r, z + (rz_new / rz) * p, rz_new, _dot(r, r), k + 1

    s = (x0, r, z, _dot(r, z), _dot(r, r), 0)
    x, _, _, _, rr, k = jax.lax.while_loop(cond, body, s)
    return x, jnp.sqrt(rr / _dot(b, b)), k


# ---------------------------------------------------------------------------
# one step
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("box", "n_correctors", "tol",
                                             "mom_maxiter", "p_maxiter"))
def step(state: dict, *, box: Box, n_correctors: int, tol: float = 1e-13,
         mom_maxiter: int = 1000, p_maxiter: int = 20000
         ) -> tuple[dict, dict]:
    """One PISO step of a dense state ``{"U", "p", "fx", "fy", "fz",
    "fb"}``, computed in the state's dtype.  Returns the new state and the
    solvers' relative residuals and iteration counts."""
    U0, p = state["U"], state["p"]
    fluxes = (state["fx"], state["fy"], state["fz"])
    M, src = momentum(box, U0, p, fluxes, state["fb"])
    U, mom_res, mom_it = jacobi(M, src, U0, tol, mom_maxiter)
    rAU = (box.h ** 3) / M.diag
    info = {"mom_res": mom_res, "mom_iters": mom_it}
    P = cond = gb = None
    for i in range(n_correctors):
        HbyA = (src - M.offdiag(U)) / M.diag[..., None]
        fH = face_flux(box, HbyA)
        fbH = boundary_flux(box, HbyA)
        if P is None:
            P, cond, gb = pressure(box, rAU)
        p, p_res, p_it = pcg(P, -divergence(fH, fbH), p, tol, p_maxiter)
        info[f"p_res_{i}"], info[f"p_iters_{i}"] = p_res, p_it
        # cond is indexed by array axis, the fluxes by coordinate
        fluxes = tuple(f - cond[AXIS[a]] * (_hi(p, AXIS[a])
                                            - _lo(p, AXIS[a]))
                       for a, f in zip("xyz", fH))
        fb = fbH + gb * jnp.stack([p[0], p[-1]])
        U = HbyA - rAU[..., None] * grad(box, p)
    fx, fy, fz = fluxes
    return {"U": U, "p": p, "fx": fx, "fy": fy, "fz": fz, "fb": fb}, info
