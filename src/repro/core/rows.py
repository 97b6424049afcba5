"""Per-part row gathers and face-to-row sums as flat 1-D gathers.

Stacked arrays carry a small leading part axis ``P``.  Indexing their row
axis directly (``x[:, idx]``) lowers on TPU to a gather whose operand and
result layouts put the part axis minor, where the ``(8, 128)`` tiling pads
it to 128 lanes: a 10-part index op then stores ~13x its data, and ~17x
for ``(P, n, 3)`` fields — at the 210³ cavity more than the chip's memory.
Folding the part axis into the index (one 1-D gather over ``P * m`` rows)
keeps every layout lane-dense.  The results are identical: the same rows,
visited in the same order.

A face-to-row sum ``x.at[:, idx].add(v)`` is written without a scatter.
A scatter-add applies its updates one after another, and on TPU each f64
update is an emulated read-modify-write, several times slower per element
than a gather.  :class:`RowSet` keeps, beside ``idx``, a static table of
the positions each row receives (built once on the host), and
:func:`add_rows` gathers the updates through it and adds them slot by slot
in ascending position order — the order a sequential scatter applies them,
so the sums are the scatter's bit for bit wherever ``x + 0.0 == x``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["RowSet", "take_rows", "add_rows"]


@dataclasses.dataclass(frozen=True, eq=False)
class RowSet:
    """A row index set ``idx`` (n,) into ``m`` rows, with its incidence.

    ``slots`` is ``(w, m)``: column ``c`` lists, ascending, the positions
    ``i`` with ``idx[i] == c``, padded with the sentinel ``n``; ``w`` is the
    most positions any row has.  Slot-major, so each slot is one ``(m,)``
    index of the kind :func:`take_rows` reads.
    """

    idx: jax.Array
    slots: np.ndarray

    @classmethod
    def build(cls, idx, m: int) -> "RowSet":
        idx = np.asarray(idx, np.int32)
        n = idx.shape[0]
        if n and (idx.min() < 0 or idx.max() >= m):
            raise ValueError(f"row ids must lie in [0, {m})")
        counts = np.bincount(idx, minlength=m)
        order = np.argsort(idx, kind="stable")   # by row, ascending within
        rows = idx[order]
        rank = np.arange(n) - (np.cumsum(counts) - counts)[rows]
        slots = np.full((int(counts.max(initial=0)), m), n, np.int32)
        slots[rank, rows] = order
        return cls(jnp.asarray(idx), slots)


def _flat(idx: jax.Array, P: int, m: int) -> jax.Array:
    """``(P * n,)`` flat row ids of ``idx`` (n,) in every part."""
    idx = jnp.asarray(idx, jnp.int32)
    return (jnp.arange(P, dtype=jnp.int32)[:, None] * m
            + idx[None, :]).reshape(-1)


def take_rows(x: jax.Array, idx: jax.Array) -> jax.Array:
    """``x[:, idx]`` for ``x`` of shape ``(P, m)`` or ``(P, m, k)``."""
    if x.ndim == 3:
        return jnp.stack([take_rows(x[..., c], idx)
                          for c in range(x.shape[2])], axis=-1)
    P, m = x.shape
    return x.reshape(-1)[_flat(idx, P, m)].reshape(P, -1)


def add_rows(x: jax.Array, rows: RowSet, v) -> jax.Array:
    """``x.at[:, rows.idx].add(v)`` for ``x`` of shape ``(P, m)`` or
    ``(P, m, k)``; ``v`` broadcasts to ``(P, n[, k])``.

    The components of a ``(P, m, k)`` field fold into the part axis, so
    one gather per slot serves all of them.  An update that does not vary
    over the faces (a conductance, a masked constant) needs no gather:
    each slot adds it where the slot is filled.
    """
    P, m = x.shape[:2]
    n = rows.idx.shape[0]
    v = jnp.asarray(v, x.dtype)
    v = v.reshape((1,) * (x.ndim - v.ndim) + v.shape)
    nv = v.shape[1]                     # 1 where v is face-invariant
    v = jnp.broadcast_to(v, (P, nv) + x.shape[2:])
    y = x
    if x.ndim == 3:                     # (P, m, k) -> (k * P, m)
        y = jnp.moveaxis(x, 2, 0).reshape(-1, m)
        v = jnp.moveaxis(v, 2, 0).reshape(-1, nv)
    if nv == 1:
        filled = rows.slots < n
        terms = [jnp.where(f, v, 0.0) for f in filled]
    else:                               # a zero at the sentinel n
        v = jnp.concatenate([v, jnp.zeros_like(v[:, :1])], axis=1)
        terms = [take_rows(v, s) for s in rows.slots]
    for t in terms:                     # ascending positions per row
        y = y + t
    if x.ndim == 3:
        return jnp.moveaxis(y.reshape(-1, P, m), 0, 2)
    return y
