"""Flat per-part row gathers/scatters == the 2-D indexing they replace."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.rows import add_rows, take_rows
from repro.fvm.mesh import CavityMesh


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
        np.testing.assert_array_equal(np.asarray(add_rows(x, idx, v)),
                                      np.asarray(x.at[:, idx].add(v)))
    # a broadcast update, as the assembly's scalar conductances are
    np.testing.assert_array_equal(np.asarray(add_rows(x, owner, 0.5)),
                                  np.asarray(x.at[:, owner].add(0.5)))
