"""Flat per-part row gathers and gathered face sums == the 2-D indexing
and scatter-adds they replace, bit for bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.rows import RowSet, add_rows, take_rows
from repro.fvm.mesh import CavityMesh, DOWN, UP, PaddedCavityMesh


@pytest.mark.parametrize("trailing", [(), (3,)])
def test_take_and_add_rows_match_2d_indexing(trailing):
    mesh = CavityMesh.cube(6, 3)
    P, m = mesh.n_parts, mesh.n_cells
    owner = jnp.asarray(mesh.owner)          # repeated, sorted rows
    neigh = jnp.asarray(mesh.neigh)          # repeated, unsorted rows
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((P, m) + trailing))
    v = jnp.asarray(rng.standard_normal((P, len(owner)) + trailing))
    for idx in (owner, neigh):
        np.testing.assert_array_equal(np.asarray(take_rows(x, idx)),
                                      np.asarray(x[:, idx]))
        np.testing.assert_array_equal(
            np.asarray(add_rows(x, RowSet.build(idx, m), v)),
            np.asarray(x.at[:, idx].add(v)))
    # a broadcast update, as the assembly's scalar conductances are
    np.testing.assert_array_equal(
        np.asarray(add_rows(x, RowSet.build(owner, m), 0.5)),
        np.asarray(x.at[:, owner].add(0.5)))


def _random_rows(m):
    """Heavy repeats into a few rows; most rows never hit."""
    rng = np.random.default_rng(1)
    return rng.choice(rng.choice(m, size=5, replace=False), size=4 * m)


_MESH = CavityMesh.cube(6, 3)
_PADDED = PaddedCavityMesh.pad(CavityMesh.cube(4, 2), 4)
SETS = {
    "owner": (_MESH.owner, _MESH.n_cells, 3),
    "neigh": (_MESH.neigh, _MESH.n_cells, 3),
    "iface_down": (_MESH.ifaces[DOWN].rows, _MESH.n_cells, 1),
    "iface_up": (_MESH.ifaces[UP].rows, _MESH.n_cells, 1),
    **{f"patch{i}": (p.rows, _MESH.n_cells, 1)
       for i, p in enumerate(_MESH.patches)},
    "padded_owner": (_PADDED.owner, _PADDED.n_cells, 3),
    "padded_iface_up": (_PADDED.ifaces[UP].rows, _PADDED.n_cells, 1),
    "padded_patch": (_PADDED.patches[-1].rows, _PADDED.n_cells, 1),
    "random": (_random_rows(40), 40, None),
}


@pytest.mark.parametrize("name", list(SETS))
def test_row_set_sums_equal_the_scatter(name):
    """Every index set the assembly builds, and a random one with rows hit
    many times and rows never hit: the gathered sums equal
    ``x.at[:, idx].add(v)`` exactly, for ``(P, m)`` and ``(P, m, 3)``
    fields, per-face, face-invariant and scalar updates."""
    idx, m, w = SETS[name]
    rows = RowSet.build(idx, m)
    n = len(idx)
    if w is None:
        assert rows.slots.shape[0] >= 5
    else:
        assert rows.slots.shape == (w, m)
    # each row lists its positions ascending, padded with n
    for c in range(m):
        got = rows.slots[:, c]
        np.testing.assert_array_equal(got[got < n], np.flatnonzero(idx == c))
    P = 3
    rng = np.random.default_rng(2)
    idx = jnp.asarray(idx)
    for trailing in ((), (3,)):
        x = jnp.asarray(rng.standard_normal((P, m) + trailing))
        for v in (rng.standard_normal((P, n) + trailing),
                  rng.standard_normal((P, 1) + trailing),
                  rng.standard_normal((n,) + trailing), 0.25):
            v = jnp.asarray(v)
            np.testing.assert_array_equal(
                np.asarray(add_rows(x, rows, v)),
                np.asarray(x.at[:, idx].add(v)))


def test_row_set_refuses_rows_out_of_range():
    with pytest.raises(ValueError, match="row ids"):
        RowSet.build(np.array([0, 4]), 4)
    empty = RowSet.build(np.zeros(0, np.int32), 4)
    x = jnp.ones((2, 4))
    np.testing.assert_array_equal(np.asarray(add_rows(x, empty, 1.0)), x)


def test_batched_add_rows_keeps_the_part_axis_in_the_index():
    """Under ``vmap`` (the cohort executors) each slot is one gather whose
    operand is ``(S, P * (n + 1))`` and whose index is flat over parts and
    rows, and the sums still equal the batched scatter's."""
    mesh = CavityMesh.cube(6, 3)
    P, m = mesh.n_parts, mesh.n_cells
    rows = RowSet.build(mesh.owner, m)
    n = len(mesh.owner)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((4, P, m)))
    v = jnp.asarray(rng.standard_normal((4, P, n)))
    f = jax.vmap(lambda a, b: add_rows(a, rows, b))
    np.testing.assert_array_equal(
        np.asarray(f(x, v)),
        np.asarray(jax.vmap(lambda a, b: a.at[:, rows.idx].add(b))(x, v)))
    eqns = jax.make_jaxpr(f)(x, v).eqns
    assert not [e for e in eqns if "scatter" in e.primitive.name]
    gathers = [e for e in eqns if e.primitive.name == "gather"]
    assert len(gathers) == rows.slots.shape[0] == 3
    for e in gathers:
        assert e.invars[0].aval.shape == (4, P * (n + 1))
        assert e.invars[1].aval.shape == (P * m, 1)
        assert e.outvars[0].aval.shape == (4, P * m)
