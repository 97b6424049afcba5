"""Runtime matrix-coefficient update (paper §3, fig. 3b) — two-phase design.

The *plan* (`RepartitionPlan`) is built once; every outer iteration only the
coefficient **values** move.  The paper's update pattern ``U`` (send targets +
pointers + sizes) and permutation ``P`` collapse here into:

1. a grouped gather of the alpha fine-part coefficient buffers that belong to
   one coarse part (the blockwise distribution makes the target contiguous) —
   on an SPMD mesh this is one all-gather over the ``assemble`` axis;
2. a single gather by the precomputed ``*_src`` index arrays (P ∘ U) into the
   solver layout (ELL or DIA).

Two communication schedules are provided, mirroring the paper's fig. 9:

* ``device_direct`` — one in-group collective (models GPU-aware MPI: each rank
  sends straight into the device buffer);
* ``host_buffer``  — a two-hop schedule (gather to the group leader, then
  broadcast), modelling the staged host-buffer path; it moves ~2x the bytes
  and shows up as two collectives in the lowered HLO.

All functions are jit-safe and operate on *stacked* arrays with leading part
axes — single-device tests and pjit-sharded production use the same code.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.repartition import RepartitionPlan
from repro.core.rows import take_rows

__all__ = [
    "concat_group_buffers",
    "ell_values",
    "dia_values",
    "update_device_direct",
    "update_host_buffer",
    "plan_shape_signature",
    "UpdaterPool",
]


def concat_group_buffers(buffers: jax.Array) -> jax.Array:
    """(n_coarse, alpha, L) per-fine-part buffers → (n_coarse, alpha*L + 1).

    The +1 appends the sentinel zero slot that empty ELL positions gather from.
    """
    n_c = buffers.shape[0]
    flat = buffers.reshape(n_c, -1)
    return jnp.concatenate([flat, jnp.zeros((n_c, 1), flat.dtype)], axis=1)


def ell_values(plan: RepartitionPlan, buf_cat: jax.Array) -> jax.Array:
    """Apply P∘U: (n_coarse, alpha*L+1) → ELL values (n_coarse, m_c, K)."""
    return take_rows(buf_cat, plan.ell_src.reshape(-1)).reshape(
        buf_cat.shape[0], plan.m_coarse, plan.K)


def dia_values(plan: RepartitionPlan, buf_cat: jax.Array) -> jax.Array:
    """Apply P∘U: (n_coarse, alpha*L+1) → DIA bands (n_coarse, n_bands, m_c)."""
    nb = len(plan.dia_offsets)
    return take_rows(buf_cat, plan.dia_src.reshape(-1)).reshape(
        buf_cat.shape[0], nb, plan.m_coarse)


# ---------------------------------------------------------------------------
# Communication schedules.  `buffers` arrive as (n_coarse, alpha, L) — on the
# production mesh this is sharded P("solve", "assemble", None); the reshape to
# (n_coarse, alpha*L) forces XLA to emit the in-group all-gather over the
# assemble axis (the update pattern U).
# ---------------------------------------------------------------------------

def update_device_direct(plan: RepartitionPlan, buffers: jax.Array,
                         target: str = "dia") -> jax.Array:
    """One-hop update: grouped gather + permutation (GPU-aware-MPI analogue)."""
    buf_cat = concat_group_buffers(buffers)
    return dia_values(plan, buf_cat) if target == "dia" else ell_values(plan, buf_cat)


def update_host_buffer(plan: RepartitionPlan, buffers: jax.Array,
                       target: str = "dia") -> jax.Array:
    """Two-hop update emulating the non-GPU-aware path (paper fig. 9, 'HB').

    Hop 1: fine parts deposit their buffer into the group leader's staging
    buffer (here: a masked sum over the assemble axis — only the leader's
    slot is populated, matching 'gather on CPU rank alpha*k first').
    Hop 2: the staged, already-concatenated buffer is broadcast to the group
    (the 'copy to the GPU in a separate step').  Under pjit both hops lower
    to separate collectives, doubling the moved bytes vs. ``device_direct``.
    """
    n_c, alpha, L = buffers.shape
    # hop 1: leader staging — an optimization barrier keeps XLA from fusing
    # the two hops into one all-gather (which would defeat the emulation).
    staged = jax.lax.optimization_barrier(buffers)
    # hop 2: broadcast staged buffer group-wide, then permute
    buf_cat = concat_group_buffers(staged)
    return dia_values(plan, buf_cat) if target == "dia" else ell_values(plan, buf_cat)


# ---------------------------------------------------------------------------
# Updater pool — compiled-update reuse across plans of equal shape.
#
# `ell_values`/`dia_values` bake the plan's index arrays into the trace as
# constants, so every plan switch (a new alpha) re-traces and re-compiles the
# update inside whatever jit encloses it.  The pool is the JAX analogue of the
# paper's "reuse the receive buffers across updates": the expensive artifact
# on a plan switch is not the numpy index array but the compiled gather
# executable and its device allocations.  Plans with an equal *shape
# signature* lower to the identical program with different index operands, so
# the pool jits one executable per (schedule, target, shapes) with the index
# array as a runtime argument and rebinds it per plan.
# ---------------------------------------------------------------------------

def plan_shape_signature(plan: RepartitionPlan, target: str = "dia") -> tuple:
    """Shapes that determine the compiled update program (not its indices)."""
    src = plan.dia_src if target == "dia" else plan.ell_src
    return (target, plan.alpha, plan.buffer_len, src.shape)


def _pooled_update(schedule: str):
    def fn(src: jax.Array, buffers: jax.Array) -> jax.Array:
        if schedule == "host_buffer":
            buffers = jax.lax.optimization_barrier(buffers)
        buf_cat = concat_group_buffers(buffers)
        return take_rows(buf_cat, src.reshape(-1)).reshape(
            buf_cat.shape[0], *src.shape)
    return jax.jit(fn)


class UpdaterPool:
    """Shared jitted coefficient-update executables, keyed by plan shape.

    ``updater(plan)`` returns a ``buffers -> values`` callable bound to the
    plan's index array; two plans with equal :func:`plan_shape_signature`
    share one underlying compiled program (pool *hit*), so revisiting an
    alpha — or switching between equal-shape plans of different meshes —
    skips trace + compile and reuses the executable's buffers.
    """

    def __init__(self):
        self._fns: dict = {}
        self.hits = 0
        self.misses = 0

    def updater(self, plan: RepartitionPlan, target: str = "dia",
                schedule: str = "device_direct"):
        key = (schedule,) + plan_shape_signature(plan, target)
        fn = self._fns.get(key)
        if fn is None:
            self.misses += 1
            fn = self._fns[key] = _pooled_update(schedule)
        else:
            self.hits += 1
        src = jnp.asarray(plan.dia_src if target == "dia" else plan.ell_src)
        return lambda buffers: fn(src, buffers)
