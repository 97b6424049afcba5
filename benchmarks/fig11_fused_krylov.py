"""Fig. 11: fused Krylov iteration core — time/iter and HBM bytes/iter.

CPU-only: each cell is a subprocess on forced host devices (a child
that needs a chip would find it held by the parent), and nothing that
runs on the chip calls this harness.

Runs the same diagonally dominant symmetric 7-band system through the CG
solver on the **reference** SolverOps backend (the seed's jnp op sequence)
and the **fused** backend (``kernels/krylov_fused``: one-pass SpMV+p.Ap,
one-pass axpy-pair+Jacobi+dots) at several repartitioning ratios alpha, on
8 forced host devices, and reports:

* ``time/iter`` — measured wall per CG iteration for both backends.  Off
  TPU the fused kernels execute through the Pallas *interpreter*, so the
  wall numbers here validate convergence parity, not kernel speed.
* ``bytes/iter`` — the per-iteration HBM traffic as
  ``Compiled.cost_analysis()`` (via ``repro.compat.cost_analysis_dict``)
  reports it for each backend's dispatch units:

  - **reference**: one CG iteration is 8 separate op dispatches (SpMV,
    p.Ap vdot, two axpys, Jacobi divide, r.z and r.r vdots, p axpy);
    each is compiled and its ``bytes accessed`` measured, then summed.
  - **fused**: the two Pallas kernels contribute their declared
    ``pl.CostEstimate`` HBM contracts (``spmv_dot_cost`` /
    ``fused_axpy_precond_cost`` — the numbers ``cost_analysis()`` reports
    for the custom calls on the TPU lowering; the interpret-mode lowering
    un-fuses the grid into HLO and multiply-counts the VMEM-resident
    operands ~3x, measured, so it cannot serve as the byte meter) plus
    the measured cost of the remaining ``p = z + beta p`` axpy.

* parity — max |x_fused - x_reference| and both iteration counts (the
  acceptance bar: <= 1e-10 with identical counts).

``--dry-run`` shrinks the mesh and writes ``BENCH_krylov.json`` (repo
root by default, ``--out`` to override) so CI can track the trajectory.

Each alpha cell is a subprocess because the forced device count must be
set before JAX initializes.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys

from benchmarks.common import emit

N_DEV = 8

CELL_CODE = r"""
import json, sys, time
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.env import enable_x64; enable_x64()
import jax.numpy as jnp
import numpy as np

from repro.compat import cost_analysis_dict
from repro.core.repartition import plan_for_mesh
from repro.fvm.mesh import CavityMesh
from repro.kernels.krylov_fused.krylov_fused import (
    fused_axpy_precond_cost, spmv_dot_cost)
from repro.solvers.cg import cg
from repro.solvers.jacobi import jacobi_preconditioner
from repro.solvers.ops import fused_stacked_ops, reference_ops
from repro.sparse.distributed import spmv_dia

alpha, n = int(sys.argv[1]), int(sys.argv[2])
mesh = CavityMesh.cube(n, 8)
plan = plan_for_mesh(mesh, alpha)
n_c = mesh.n_parts // alpha
m_c, plane = plan.m_coarse, plan.plane
offsets = tuple(int(o) for o in plan.dia_offsets)
N = n_c * m_c

# symmetric diagonally dominant 7-band system on the global index space:
# A[i, i+off] = A[i+off, i] = -w_off[i], diag = 1 + |row|
rng = np.random.default_rng(11)
bands_g = np.zeros((len(offsets), N))
for d, off in enumerate(offsets):
    if off <= 0:
        continue
    w = rng.uniform(0.05, 1.0, N - off)
    bands_g[d, :N - off] = -w                      # A[i, i+off]
    bands_g[offsets.index(-off), off:] = -w        # A[i+off, i]
diag_g = 1.0 + np.abs(bands_g).sum(axis=0)
bands_g[offsets.index(0)] = diag_g
bands = jnp.asarray(bands_g.reshape(len(offsets), n_c, m_c).transpose(1, 0, 2))
diag = jnp.asarray(diag_g.reshape(n_c, m_c))
x_true = jnp.asarray(rng.standard_normal((n_c, m_c)))

A = lambda v: spmv_dia(bands, v, offsets=offsets, plane=plane)
b = A(x_true)
x0 = jnp.zeros_like(b)

ops_ref = reference_ops(A, jacobi_preconditioner(diag))
ops_fus = fused_stacked_ops(bands, diag, offsets=offsets, plane=plane)

solve_ref = jax.jit(lambda b, x0: cg(ops_ref, b, x0, tol=1e-9, maxiter=2000))
solve_fus = jax.jit(lambda b, x0: cg(ops_fus, b, x0, tol=1e-9, maxiter=2000))


def timed(fn):
    res = jax.block_until_ready(fn(b, x0))  # warm-up / compile
    t0 = time.perf_counter()
    res = jax.block_until_ready(fn(b, x0))
    return res, time.perf_counter() - t0


res_r, t_r = timed(solve_ref)
res_f, t_f = timed(solve_fus)
iters_r, iters_f = int(res_r.iters), int(res_f.iters)
max_diff = float(jnp.abs(res_f.x - res_r.x).max())

# ---- bytes/iter -----------------------------------------------------------
def measured_bytes(fn, *args):
    c = jax.jit(fn).lower(*args).compile()
    return float(cost_analysis_dict(c).get("bytes accessed", 0.0))

vd = lambda a, c: jnp.vdot(a, c, precision=jax.lax.Precision.HIGHEST)
sc = jnp.asarray(0.5)
y = A(b)
# the reference backend's 8 per-iteration op dispatches
ref_stages = {
    "spmv": measured_bytes(lambda b_, x_: spmv_dia(
        b_, x_, offsets=offsets, plane=plane), bands, b),
    "dot_pAp": measured_bytes(vd, b, y),
    "axpy_x": measured_bytes(lambda x_, p_, a_: x_ + a_ * p_, b, y, sc),
    "axpy_r": measured_bytes(lambda r_, ap_, a_: r_ - a_ * ap_, b, y, sc),
    "precond": measured_bytes(lambda r_, d_: r_ / d_, b, diag),
    "dot_rz": measured_bytes(vd, b, y),
    "dot_rr": measured_bytes(vd, b, b),
    "axpy_p": measured_bytes(lambda z_, p_, b_: z_ + b_ * p_, b, y, sc),
}
bytes_ref = sum(ref_stages.values())

# the fused backend: two kernel contracts (= cost_analysis of the TPU
# custom calls) + the measured p axpy
k1 = n_c * spmv_dot_cost(len(offsets), m_c, plane)["bytes_accessed"]
k2 = n_c * fused_axpy_precond_cost(m_c)["bytes_accessed"]
axpy_p = measured_bytes(lambda z_, p_, b_: z_ + b_ * p_, b, y, sc)
bytes_fus = k1 + k2 + axpy_p

# ---- mixed-precision policies ---------------------------------------------
# Same system under each PrecisionPolicy, normalized rhs + tol=1e-12 so
# the 1e-10 parity gate is an absolute-error statement.  The refined
# solves run the jnp reference closures (the inner sweep at the storage
# dtype, outer f64 replay); bytes/iter is the fused kernels' declared
# per-policy HBM contract — inner iterations stream storage-width values,
# partial slots write at the accum width.
from repro.solvers.jacobi import safe_jacobi_inverse
from repro.solvers.precision import POLICIES

b_n = b / jnp.sqrt(vd(b, b))
x0n = jnp.zeros_like(b_n)


def policy_ops(pol):
    if not pol.refine:
        return ops_ref
    bands_lo = bands.astype(pol.storage_dtype)
    diag_lo = diag.astype(pol.storage_dtype)
    A_lo = lambda v: spmv_dia(bands_lo, v, offsets=offsets, plane=plane)
    return reference_ops(A_lo, jacobi_preconditioner(diag_lo), policy=pol,
                         matvec_hi=A)


policies = {}
x64 = None
for name in ("f64", "f32_ir", "bf16_ir"):
    pol = POLICIES[name]
    solve = jax.jit(lambda b_, x_, o=policy_ops(pol):
                    cg(o, b_, x_, tol=1e-12, maxiter=4000))
    res = jax.block_until_ready(solve(b_n, x0n))    # warm-up / compile
    t0 = time.perf_counter()
    res = jax.block_until_ready(solve(b_n, x0n))
    t = time.perf_counter() - t0
    if name == "f64":
        x64 = res.x
    it = max(int(res.iters), 1)
    k1p = n_c * spmv_dot_cost(len(offsets), m_c, plane,
                              itemsize=pol.storage_itemsize,
                              accum_itemsize=pol.accum_itemsize)[
                                  "bytes_accessed"]
    k2p = n_c * fused_axpy_precond_cost(m_c, itemsize=pol.storage_itemsize,
                                        accum_itemsize=pol.accum_itemsize)[
                                            "bytes_accessed"]
    policies[name] = {
        "inner_iters": int(res.iters),
        "outer_iters": int(res.outer_iters),
        "converged": bool(res.converged),
        "residual": float(res.residual),
        "max_diff_vs_f64": float(jnp.abs(res.x - x64).max()),
        "time_per_iter_us": 1e6 * t / it,
        "bytes_per_iter": k1p + k2p + axpy_p * pol.storage_itemsize / 8.0,
    }

print(json.dumps({
    "alpha": alpha, "n": n, "n_coarse": n_c, "m_coarse": m_c,
    "iters": {"reference": iters_r, "fused": iters_f},
    "max_diff": max_diff,
    "residual": {"reference": float(res_r.residual),
                 "fused": float(res_f.residual)},
    "time_per_iter_us": {"reference": 1e6 * t_r / max(iters_r, 1),
                         "fused": 1e6 * t_f / max(iters_f, 1)},
    "bytes_per_iter": {"reference": bytes_ref, "fused": bytes_fus,
                       "reference_stages": ref_stages,
                       "fused_kernels": {"spmv_dot": k1,
                                         "axpy_precond_dots": k2,
                                         "axpy_p": axpy_p}},
    "bytes_ratio": bytes_ref / bytes_fus,
    "policies": policies,
}))
"""


def run(n: int = 24, alphas=(1, 2, 4), out: str | None = None,
        dry_run: bool = False) -> dict:
    if dry_run:
        n = min(n, 16)
    env = dict(os.environ)
    env.setdefault("PYTHONPATH", "src")
    cells = []
    for alpha in alphas:
        r = subprocess.run(
            [sys.executable, "-c", CELL_CODE, str(alpha), str(n)],
            capture_output=True, text=True, env=env, timeout=2400)
        tag = f"fig11_fused_krylov_alpha{alpha}"
        if r.returncode != 0:
            emit(f"{tag}_ERROR", 0.0, r.stderr.strip()[-140:])
            continue
        rec = json.loads(r.stdout.strip().splitlines()[-1])
        cells.append(rec)
        t = rec["time_per_iter_us"]
        emit(tag, t["fused"] * 1e-6,
             f"ref={t['reference']:.0f}us/it fused={t['fused']:.0f}us/it "
             f"bytes_ratio={rec['bytes_ratio']:.2f}x "
             f"iters={rec['iters']['reference']}/{rec['iters']['fused']} "
             f"maxdiff={rec['max_diff']:.1e}")
        for name, p in rec.get("policies", {}).items():
            emit(f"{tag}_{name}", p["time_per_iter_us"] * 1e-6,
                 f"inner={p['inner_iters']} outer={p['outer_iters']} "
                 f"bytes/it={p['bytes_per_iter']:.2e} "
                 f"diff_vs_f64={p['max_diff_vs_f64']:.1e}")
    report = {
        "bench": "fig11_fused_krylov",
        "n_forced_devices": N_DEV,
        "method": {
            "bytes_per_iter": (
                "sum over the backend's per-iteration dispatch units via "
                "repro.compat.cost_analysis_dict: reference = the 8 "
                "separate jnp op dispatches of one CG iteration, each "
                "compiled and measured; fused = the two krylov_fused "
                "kernels' declared pl.CostEstimate HBM contracts (what "
                "cost_analysis reports for the custom calls on the TPU "
                "lowering; the interpret-mode lowering un-fuses the grid "
                "and inflates static counts ~3x) + the measured p axpy"),
            "time_per_iter": ("wall of the jitted CG solve / iteration "
                              "count; off-TPU the fused kernels run in "
                              "the Pallas interpreter"),
            "policies": (
                "per-PrecisionPolicy columns on the same system with a "
                "normalized rhs at tol=1e-12: inner/outer iteration "
                "split of the iterative-refinement loop, max |x - x_f64| "
                "(the 1e-10 parity gate), and the fused kernels' "
                "declared per-policy bytes/iter (storage-width streams, "
                "accum-width partial slots)"),
        },
        "cells": cells,
    }
    if out:
        pathlib.Path(out).write_text(json.dumps(report, indent=2) + "\n")
        emit("fig11_fused_krylov_json", 0.0, f"wrote {out}")
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry-run", action="store_true",
                    help="small mesh + write BENCH_krylov.json")
    ap.add_argument("--n", type=int, default=24, help="cells per axis")
    ap.add_argument("--alphas", default="1,2,4")
    ap.add_argument("--out", default=None,
                    help="JSON report path (default: BENCH_krylov.json at "
                         "the repo root when --dry-run)")
    args = ap.parse_args()
    out = args.out
    if out is None and args.dry_run:
        out = str(pathlib.Path(__file__).resolve().parent.parent
                  / "BENCH_krylov.json")
    alphas = tuple(int(a) for a in args.alphas.split(","))
    print("name,us_per_call,derived")
    run(n=args.n, alphas=alphas, out=out, dry_run=args.dry_run)


if __name__ == "__main__":
    main()
