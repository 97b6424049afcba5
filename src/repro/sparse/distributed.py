"""Distributed SpMV over stacked part arrays with z-slab halo exchange.

Arrays are stacked over the part axis (axis 0).  Under ``jax.jit`` with the
part axis sharded over a mesh axis, the static shifts in
:func:`halo_exchange` lower to ``collective-permute`` — exactly the
neighbour exchange the paper's distributed SpMV performs — and the dot
products in the Krylov solvers lower to ``all-reduce``.  The same code runs
unsharded in tests.

Two matrix targets (see :mod:`repro.core.repartition`):

* **DIA** — 7-band storage; SpMV is seven shifted multiply-adds on an
  ``x_pad = [down-halo | x | up-halo]`` vector: fully vectorizable on the TPU
  VPU, no gather.  This is the production path (Pallas kernel in
  ``repro.kernels.spmv_dia``).
* **ELL** — padded rows with explicit column indices into
  ``x_ext = [x | down-halo | up-halo]``; general but gather-based.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["halo_exchange", "spmv_dia", "spmv_ell", "x_pad", "x_pad_lanes",
           "x_ext", "difference_form"]

LANES = 128


def shift_planes(first: jax.Array, last: jax.Array
                 ) -> tuple[jax.Array, jax.Array]:
    """(down_halo, up_halo) from every part's first and last plane.

    ``down_halo[p] = last[p-1]`` (zeros for p=0) and ``up_halo[p] =
    first[p+1]`` (zeros for p=P-1).  Under a sharded part axis this is a
    collective-permute shift; at the physical boundary the halo is zero —
    matching the zero interface coefficients there, so the product is
    exact.
    """
    zeros = jnp.zeros((1, first.shape[1]), dtype=first.dtype)
    down = jnp.concatenate([zeros, last[:-1]], axis=0)
    up = jnp.concatenate([first[1:], zeros], axis=0)
    return down, up


def halo_exchange(x: jax.Array, plane: int) -> tuple[jax.Array, jax.Array]:
    """Neighbour planes for every part: (down_halo, up_halo), each (P, plane).

    ``down_halo[p] = x[p-1, -plane:]``, ``up_halo[p] = x[p+1, :plane]``
    (:func:`shift_planes`).
    """
    return shift_planes(x[:, :plane], x[:, -plane:])


def x_pad(x: jax.Array, plane: int) -> jax.Array:
    """[down-halo | x | up-halo] layout for DIA shifts; (P, m + 2*plane)."""
    down, up = halo_exchange(x, plane)
    return jnp.concatenate([down, x, up], axis=1)


def x_pad_lanes(x: jax.Array, *, m: int, plane: int, lead: int,
                x_len: int) -> jax.Array:
    """``x_pad`` in the Pallas kernels' lane-dense layout, from lane-dense
    parts (``repro.kernels.spmv_dia.spmv_dia.LaneLayout``).

    ``x``: (P, R, 128), the real rows first (rows from ``m`` on are
    ignored).  Returns (P, x_len/128, 128): zeros, the down halo ending at
    row ``lead``, the part's whole 128-row groups unmoved from row
    ``lead`` on (``lead`` is tile-aligned), then the last partial group,
    the up halo right after row ``m - 1``, and zeros.  Only the halo
    planes and the last partial group change lanes.
    """
    P = x.shape[0]

    def flat(a):
        return a.reshape(P, -1)

    q, rem = divmod(m, LANES)
    first = flat(x[:, :-(-plane // LANES)])[:, :plane]
    j0 = (m - plane) // LANES
    last = flat(x[:, j0:-(-m // LANES)])[:, m - plane - j0 * LANES:]
    down, up = shift_planes(first, last[:, :plane])
    head = jnp.concatenate(
        [jnp.zeros((P, lead - plane), x.dtype), down], axis=1)
    tail = [flat(x[:, q:q + 1])[:, :rem]] if rem else []
    fill = x_len - lead - m - plane
    tail = jnp.concatenate(tail + [up, jnp.zeros((P, fill), x.dtype)], axis=1)
    return jnp.concatenate([head.reshape(P, -1, LANES), x[:, :q],
                            tail.reshape(P, -1, LANES)], axis=1)


def x_ext(x: jax.Array, plane: int) -> jax.Array:
    """[x | down-halo | up-halo] layout for ELL columns; (P, m + 2*plane)."""
    down, up = halo_exchange(x, plane)
    return jnp.concatenate([x, down, up], axis=1)


def difference_form(bands: jax.Array, offsets: tuple[int, ...]) -> jax.Array:
    """DIA bands with the offset-0 band replaced by the row sums.

    ``A x = s * x + sum_{d != 0} a_d * (x_{i+d} - x_i)`` with ``s`` the row
    sum: the same operator as the plain form.  Taken at full precision and
    then rounded, it maps the constant vector to the (rounded) row sums
    exactly, whereas plain-form bands rounded one by one leave row sums of
    order ``eps * diag``.  For the reference-pinned all-Neumann pressure
    matrix (row sums zero but at the reference cell) those errors add up
    over the mesh: at the 210³ cavity they move ``1ᵀ A 1`` in f32 by 60%,
    and f32 sweeps in the plain form diverge.  Works on ``(..., nb, m)``.
    """
    c = offsets.index(0)
    return bands.at[..., c, :].set(jnp.sum(bands, axis=-2))


@functools.partial(jax.jit, static_argnames=("offsets", "plane",
                                             "difference"))
def spmv_dia(bands: jax.Array, x: jax.Array, *, offsets: tuple[int, ...],
             plane: int, difference: bool = False) -> jax.Array:
    """Banded SpMV: y[p, i] = sum_d bands[p, d, i] * x_pad[p, plane + i + off_d].

    bands: (P, n_bands, m); x: (P, m).  Offsets are static ⇒ each band is a
    static slice of x_pad — no gather, pure FMA chains (TPU-native).
    ``difference``: the bands are in :func:`difference_form` (the
    off-diagonal terms act on ``x_{i+d} - x_i``).
    """
    P, nb, m = bands.shape
    xp = x_pad(x, plane)
    y = jnp.zeros_like(x)
    for d, off in enumerate(offsets):
        xd = jax.lax.dynamic_slice_in_dim(xp, plane + off, m, axis=1)
        if difference and off:
            xd = xd - x
        y = y + bands[:, d, :] * xd
    return y


@functools.partial(jax.jit, static_argnames=("plane",))
def spmv_ell(vals: jax.Array, cols: jax.Array, x: jax.Array, *,
             plane: int) -> jax.Array:
    """Padded-ELL SpMV: y[p,i] = sum_k vals[p,i,k] * x_ext[p, cols[i,k]].

    vals: (P, m, K); cols: (m, K) shared across parts (plan uniformity);
    x: (P, m).  Gather-based general path (oracle for the DIA/Pallas paths).
    """
    xe = x_ext(x, plane)                       # (P, m + 2*plane)
    gathered = jnp.take(xe, cols.reshape(-1), axis=1)  # (P, m*K)
    gathered = gathered.reshape(x.shape[0], *cols.shape)
    return jnp.einsum("pik,pik->pi", vals, gathered)
