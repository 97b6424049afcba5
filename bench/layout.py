"""The program's stacked state layout, and the dense layout of the reference.

The program holds a state of the ``n``-cube split into ``parts`` z-slabs as
``(U, p, phi, phi_if, phi_b)``:

* ``U`` ``(P, m, 3)`` and ``p`` ``(P, m)``, cell ``i + nx (j + ny kl)`` of
  slab ``s`` at row ``s m + that``, so a reshape to ``(nz, ny, nx)`` is the
  dense field;
* ``phi`` ``(P, F)``: the slab-internal faces, ordered by their lower cell
  and, for one cell, its +x, +y, +z face (those that exist);
* ``phi_if`` ``(P, 2, nx ny)``: the outward fluxes through a slab's lower
  (slot 0) and upper (slot 1) plane where another slab lies, else 0;
* ``phi_b`` ``(P, 2, nx ny)``: the outward fluxes through the z0 face (slot
  0 of slab 0) and the z1 face (slot 1 of the last slab), else 0.

The dense layout is the one of :mod:`reference.piso`.  This module only
moves numbers between the two; it computes nothing.
"""
from __future__ import annotations

import functools

import numpy as np

DOWN, UP = 0, 1


@functools.lru_cache(maxsize=4)
def face_index(n: int, parts: int):
    """Positions of the x, y and z faces of one slab in its ``phi`` row,
    each in the C order of its dense block."""
    nzl = n // parts
    kl, j, i = np.meshgrid(np.arange(nzl), np.arange(n), np.arange(n),
                           indexing="ij")
    has = [(i < n - 1).ravel(), (j < n - 1).ravel(), (kl < nzl - 1).ravel()]
    count = has[0].astype(np.int64) + has[1] + has[2]
    start = np.cumsum(count) - count
    ix = start[has[0]]
    iy = (start + has[0])[has[1]]
    iz = (start + has[0] + has[1])[has[2]]
    return ix, iy, iz


def to_dense(state: dict, n: int, parts: int) -> dict:
    """The dense fields of a program state given as host arrays by name."""
    nzl = n // parts
    ix, iy, iz = face_index(n, parts)
    phi, phi_if, phi_b = state["phi"], state["phi_if"], state["phi_b"]
    fx = phi[:, ix].reshape(n, n, n - 1)
    fy = phi[:, iy].reshape(n, n - 1, n)
    fz = np.zeros((n - 1, n, n), phi.dtype)
    for s in range(parts):
        k0 = s * nzl
        fz[k0:k0 + nzl - 1] = phi[s, iz].reshape(nzl - 1, n, n)
        if s < parts - 1:
            fz[k0 + nzl - 1] = phi_if[s, UP].reshape(n, n)
    return {
        # the upper slab's copy of each interface flux (the dense fz
        # holds the lower slab's), so that the check reads both
        "fz_if_down": -phi_if[1:, DOWN].reshape(parts - 1, n, n),
        "U": state["U"].reshape(n, n, n, 3),
        "p": state["p"].reshape(n, n, n),
        "fx": fx, "fy": fy, "fz": fz,
        "fb": np.stack([phi_b[0, DOWN].reshape(n, n),
                        phi_b[parts - 1, UP].reshape(n, n)]),
    }


def interface_planes(n: int, parts: int) -> np.ndarray:
    """The rows of a dense ``fz`` that are interfaces between slabs."""
    nzl = n // parts
    return np.arange(1, parts) * nzl - 1
