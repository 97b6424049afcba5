"""Pluggable per-iteration operation backends for the Krylov solvers.

One solver body (``cg``/``bicgstab``) runs over a :class:`SolverOps`
bundle, so the stacked, single-device and full-mesh layouts — and the
reference-jnp vs fused-Pallas implementations — all share the same control
flow and the same convergence decisions:

* ``matvec(x)``            — ``A x`` (operator apply, halo exchange inside)
* ``precond(r)``           — ``M^-1 r`` (Jacobi here)
* ``matvec_dot(p)``        — ``(A p, p . A p)``; fused backends compute the
  dot's block partials in the same HBM pass as the SpMV
* ``fused_step(x, r, p, Ap, alpha)`` — ``(x', r', z, r'.z, r'.r')``: the
  axpy pair, the preconditioner apply and both reductions of the second
  half of a CG iteration
* ``dots(*pairs)``         — a tuple of global vdots (initial residual,
  BiCGStab's rho/rv/ts/tt)

Backends:

* :func:`reference_ops` — plain jnp over any ``A``/``M`` closures; the op
  sequence is exactly the seed solver's, so numerics are unchanged.
* :func:`fused_stacked_ops` — the ``kernels/krylov_fused`` Pallas pair on
  stacked DIA bands (interpret mode off-TPU).
* the full-mesh fused backend lives in
  ``repro.sparse.shardmap_spmv.make_fused_ops_full_mesh`` (shard_map +
  per-shard kernels + psum'd partials).

**Precision.**  Both constructors take a
:class:`repro.solvers.precision.PrecisionPolicy`.  Under the default
``f64`` policy every cast below is a no-op: the policy adds nothing to
the plain op sequence.  Under a refined policy
(``f32_ir`` / ``bf16_ir``) the bundle's members run the *inner* sweep at
the storage dtype with accum-dtype reductions, and the bundle carries a
``matvec_hi`` closure over the original f64 bands for the outer
residual replay ``r = b - A x`` in the solvers' iterative-refinement
loop.

Selection is **per part size, platform and precision**
(:func:`resolve_backend`): the
fused kernels pay off once a part fills at least one ``block_rows`` grid
step; below that (tiny test meshes, deeply fused full-mesh shards) the
reference path wins on dispatch overhead, so ``"auto"`` keeps it.  Off-TPU
``"auto"`` always keeps the reference path — the kernels would execute
through the Pallas *interpreter* inside the jitted ``while_loop`` (a
Python-level emulation, ~50x wall overhead on host devices) — while an
explicit ``"fused"`` request still forces them (parity tests, benchmarks).
The crossover row count defaults to :data:`FUSED_MIN_ROWS` but is a
parameter, overridable per call or process-wide via the
``REPRO_FUSED_MIN_ROWS`` environment variable (see ``docs/kernels.md``).
On TPU the kernels take no 8-byte dtype: the ``f64`` policy resolves
``"auto"`` to ``reference`` and refuses an explicit ``"fused"``.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable

import jax
import jax.numpy as jnp

from repro.solvers.precision import F64, PrecisionPolicy, get_policy

__all__ = [
    "SolverOps", "reference_ops", "stacked_reference_ops",
    "fused_stacked_ops", "resolve_backend",
    "FUSED_MIN_ROWS", "BACKENDS", "vdot",
]

BACKENDS = ("auto", "fused", "reference")

# the fused kernels start paying off once a part fills one default row
# block (below this the grid is a single padded step and per-call overhead
# dominates); "auto" switches backends at this part size.  Default for the
# resolve_backend parameter; REPRO_FUSED_MIN_ROWS overrides process-wide.
FUSED_MIN_ROWS = 2048


def vdot(a: jax.Array, b: jax.Array) -> jax.Array:
    """Global dot product as an elementwise multiply and a sum.

    Exact at the operands' precision on every backend: a TPU
    ``dot_general`` would emulate an f64 dot by splitting each operand
    into eight f32 pieces held in memory at once (at the 210³ cavity the
    f64 momentum solve then needs more than the chip's 16 GB), and would
    need ``Precision.HIGHEST`` to keep f32 out of bf16 passes.
    """
    return jnp.sum(a * b)


@dataclasses.dataclass(frozen=True)
class SolverOps:
    """The per-iteration operation bundle consumed by ``cg``/``bicgstab``."""

    matvec: Callable
    precond: Callable
    matvec_dot: Callable
    fused_step: Callable
    dots: Callable
    backend: str = "reference"   # informational (logs, benchmarks)
    # mixed-precision contract: the policy the members were built under,
    # and (for refined policies) the full-precision operator for the
    # outer residual replay.  None falls back to ``matvec`` — correct for
    # f64, required for f32_ir/bf16_ir bundles built from downcast bands.
    policy: PrecisionPolicy = F64
    matvec_hi: Callable | None = None
    # the members' vector layout: ``pack`` maps an outer (P, m) vector
    # into it and ``unpack`` back; the solver sweeps run between the two.
    # Identity except for the fused stacked backend, whose vectors are
    # the kernels' lane-dense (P, m_pad/128, 128) with zero pad rows (the
    # bands and Jacobi inverse are zero there, so r and z stay zero; x
    # and p may not, and no member reads them).
    pack: Callable = lambda v: v
    unpack: Callable = lambda v: v


def resolve_backend(requested: str, m: int, on_tpu: bool | None = None,
                    fused_min_rows: int | None = None,
                    precision: PrecisionPolicy | str = F64) -> str:
    """Concrete backend for a part of ``m`` rows (see module doc).

    ``on_tpu`` overrides the platform probe (tests); ``None`` asks JAX.
    ``fused_min_rows`` sets the auto-mode crossover row count; ``None``
    reads ``REPRO_FUSED_MIN_ROWS`` from the environment, falling back to
    :data:`FUSED_MIN_ROWS`.  ``precision`` is the solve's policy: the
    Pallas TPU kernels take no 8-byte dtype, so on TPU the ``f64`` policy
    resolves ``auto`` to ``reference`` and refuses ``fused``.
    """
    if requested not in BACKENDS:
        raise ValueError(f"unknown solver backend {requested!r}")
    if on_tpu is None:
        on_tpu = jax.default_backend() == "tpu"
    wide = get_policy(precision).storage_itemsize > 4
    if requested == "fused" and on_tpu and wide:
        raise ValueError(
            "solver backend 'fused' with the f64 precision policy cannot "
            "run on TPU: Pallas TPU kernels take no f64 (an f64 custom "
            "call is unimplemented). Use --precision f32_ir or bf16_ir, "
            "or the reference backend.")
    if requested != "auto":
        return requested
    if fused_min_rows is None:
        fused_min_rows = int(os.environ.get("REPRO_FUSED_MIN_ROWS",
                                            FUSED_MIN_ROWS))
    if not on_tpu or wide:
        return "reference"
    return "fused" if m >= fused_min_rows else "reference"


def _reference_dots(*pairs):
    return tuple(vdot(a, b) for a, b in pairs)


def _policy_dot(policy: PrecisionPolicy):
    """Per-policy global vdot: upcast both operands to the accum dtype.

    The f64 policy returns the plain :func:`vdot` (no casts at all), so
    legacy closures of any dtype keep their exact pre-policy reduction.
    """
    if not policy.refine and policy.name == "f64":
        return vdot
    acc = policy.accum_dtype

    def dot(a, b):
        return vdot(a.astype(acc), b.astype(acc))

    return dot


def reference_ops(A: Callable, M: Callable | None = None, *,
                  policy: PrecisionPolicy | str = F64,
                  matvec_hi: Callable | None = None) -> SolverOps:
    """Plain-jnp backend over operator closures (any layout).

    The ``fused_step``/``matvec_dot`` members run the seed solver's exact
    op sequence, so a refactored solver body on this backend is
    numerically identical to the pre-``SolverOps`` implementation.

    Under a refined ``policy`` the caller passes closures over the
    *downcast* operator (``A``/``M`` at the storage dtype) plus a
    ``matvec_hi`` over the original f64 bands; the reductions then
    accumulate at the policy's accum dtype.
    """
    policy = get_policy(policy)
    M = M if M is not None else (lambda r: r)
    dot = _policy_dot(policy)

    def matvec_dot(p):
        Ap = A(p)
        return Ap, dot(p, Ap)

    def fused_step(x, r, p, Ap, alpha):
        a = alpha.astype(x.dtype)  # accum scalar -> storage (f64: no-op)
        xn = x + a * p
        rn = r - a * Ap
        z = M(rn)
        return xn, rn, z, dot(rn, z), dot(rn, rn)

    def dots(*pairs):
        return tuple(dot(a, b) for a, b in pairs)

    return SolverOps(matvec=A, precond=M, matvec_dot=matvec_dot,
                     fused_step=fused_step, dots=dots,
                     backend="reference", policy=policy,
                     matvec_hi=matvec_hi)


def stacked_reference_ops(bands: jax.Array, diag: jax.Array, *,
                          offsets: tuple[int, ...], plane: int,
                          policy: PrecisionPolicy | str = F64) -> SolverOps:
    """Plain-jnp backend on stacked DIA bands ``(P, nb, m)``.

    Under a refined ``policy`` the inner sweep runs over the bands in
    :func:`~repro.sparse.distributed.difference_form`, downcast to the
    storage dtype, and ``matvec_hi`` over the original bands replays the
    outer f64 residual (the parity gate).
    """
    from repro.solvers.jacobi import jacobi_preconditioner
    from repro.sparse.distributed import difference_form, spmv_dia

    policy = get_policy(policy)

    def A_hi(x):
        return spmv_dia(bands, x, offsets=offsets, plane=plane)

    if not policy.refine:
        return reference_ops(A_hi, jacobi_preconditioner(diag))
    bands_lo = difference_form(bands, offsets).astype(policy.storage_dtype)

    def A_lo(x):
        return spmv_dia(bands_lo, x, offsets=offsets, plane=plane,
                        difference=True)

    return reference_ops(
        A_lo, jacobi_preconditioner(diag.astype(policy.storage_dtype)),
        policy=policy, matvec_hi=A_hi)


def fused_stacked_ops(bands: jax.Array, diag: jax.Array, *,
                      offsets: tuple[int, ...], plane: int,
                      block_rows: int = 0,
                      policy: PrecisionPolicy | str = F64) -> SolverOps:
    """Fused-Pallas backend on stacked DIA bands ``(P, nb, m)``.

    ``diag`` is the stacked matrix diagonal (P, m); the Jacobi inverse is
    precomputed once and folded into the fused update kernel.  Zero
    diagonal entries (the ragged-tail zero padding: a part size not
    divisible by ``block_rows`` pads rows whose diag is exactly 0.0)
    invert to a safe 0 — a bare ``1/diag`` would carry ``inf`` into the
    padded lanes, where the first fused Jacobi apply turns ``inf * 0``
    into NaN and poisons every global reduction of the solve.

    Under a refined ``policy`` the bands are put in
    :func:`~repro.sparse.distributed.difference_form` at full precision
    and, with diag, downcast once to the storage dtype for the kernel hot
    loop (this is the bytes/iter win: the kernels stream 4- or 2-byte
    values), the block partials accumulate at the accum dtype, and
    ``matvec_hi`` keeps a jnp SpMV over the original full-precision bands
    for the outer residual replay.
    """
    from repro.kernels.krylov_fused.ops import (fused_matvec_dot,
                                                fused_update_step)
    from repro.kernels.spmv_dia.ops import spmv_dia_pallas
    from repro.kernels.spmv_dia.spmv_dia import lane_layout, pick_block_rows
    from repro.sparse.distributed import difference_form, spmv_dia
    from repro.solvers.jacobi import safe_jacobi_inverse

    policy = get_policy(policy)
    bands_hi = bands
    accum = None
    if policy.name != "f64":
        bands = difference_form(bands, offsets).astype(policy.storage_dtype)
        diag = diag.astype(policy.storage_dtype)
        accum = policy.accum

    # the kernels' lane-dense layout: bands and the Jacobi inverse once
    # per system, every Krylov vector once per sweep (pack/unpack)
    m = bands.shape[-1]
    layout = lane_layout(m, plane, block_rows or pick_block_rows(
        m, bands.dtype), bands.dtype)
    bands = layout.pack(bands)
    inv = layout.pack(safe_jacobi_inverse(diag))
    band_kw = dict(offsets=offsets, layout=layout, accum_dtype=accum,
                   difference=accum is not None)

    def matvec(x):
        return spmv_dia_pallas(bands, x, **band_kw)

    def precond(r):
        return r * inv

    def matvec_dot(p):
        return fused_matvec_dot(bands, p, **band_kw)

    def fused_step(x, r, p, Ap, alpha):
        return fused_update_step(x, r, p, Ap, inv, alpha,
                                 block_rows=layout.block_rows,
                                 accum_dtype=accum)

    matvec_hi = None
    if policy.refine:
        def matvec_hi(x):
            return spmv_dia(bands_hi, x, offsets=offsets, plane=plane)

    dots = _reference_dots if policy.name == "f64" else (
        lambda *pairs: tuple(_policy_dot(policy)(a, b) for a, b in pairs))

    return SolverOps(matvec=matvec, precond=precond, matvec_dot=matvec_dot,
                     fused_step=fused_step, dots=dots,
                     backend="fused", policy=policy, matvec_hi=matvec_hi,
                     pack=layout.pack, unpack=layout.unpack)
