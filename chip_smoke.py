#!/usr/bin/env python3
"""Chip smoke run: the repartitioned PISO cavity end to end on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the full-mesh solve, four chips

A smoke run, not a benchmark: it shows that the main path compiles and
runs on the chip and agrees with the on-chip f64 reference.  The times it
prints are for orientation only.

One chip (the default):

1. kernel parity — the lane-dense Pallas kernels against their ``ref.py``
   oracles on a small ragged part (f32 and bf16 storage) and on one
   coarse part of the 210³ cavity (1,852,200 rows, f32), with the bands
   in the difference form the refined solves use;
2. the paper's lidDrivenCavity3D at n = 210 (210³ ≈ 9.26 M cells),
   ``--parts 10 --alpha 2`` (the pressure system fuses pairs of fine
   parts): three PISO steps through ``make_solver``/``run_steps`` at
   ``--precision f32_ir``, which on a TPU puts the fused Pallas kernels
   in the Krylov loops (checked) with the f64 refinement in XLA;
3. the first of those steps at ``--precision f64 --solver-backend
   reference`` (XLA f64), and the fields of the two runs after it
   compared within the tier-1 refined-policy step gate (``atol=1e-8``,
   ``rtol=1e-7``).  One step only: a 210³ step takes ~150 s (f32_ir)
   to ~200 s (f64) on a v5e, mostly outside the Krylov loops, so 3 + 3
   steps do not fit the 1,200 s limit; and at ``mom_tol=1e-11`` the
   plain f64 BiCGStab of the second step stalls at its 500-iteration cap
   on a v5e (the refined f32_ir momentum solves reach it in ~100).

Both 210³ runs solve to ``--p-tol 1e-12 --mom-tol 1e-11`` instead of the
launcher's defaults (1e-8, 1e-7): two trajectories that each stop at a
relative residual ``tol`` differ by up to about ``cond * tol``, which at
the defaults is beyond the step gate from n = 20 up on any backend.  The
launcher reproduces either run:
``python -m repro.launch.case --n 210 --parts 10 --alpha 2 --steps 3
--precision f32_ir --p-tol 1e-12 --mom-tol 1e-11``.

Four chips (``--four-chips``, only this phase): ``solve_mode=full_mesh``
on a (2, 2) mesh of four chips against the stacked run of the same case
on one chip, both in XLA f64 (the full-mesh path is f64-only), one step
at n = 252 with 4 parts and alpha 2: the largest n divisible by 4 whose
stacked step fits one chip (14.9 GB of 16 in a compile for a described
v5e; 280³ full_mesh needs 17.7 GB per chip, since it shards only the
solve).  p_iters are printed side by side; the fields must agree within
1e-10.

Every step must leave finite fields and no Krylov solve unconverged or at
its cap.  Any failure exits non-zero before the last line, which is
otherwise one JSON object: ``{"ok": true, "device": {"platform", "kind",
"count"}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
N, PARTS, ALPHA, STEPS, REF_STEPS = 210, 10, 2, 3, 1
P_TOL, MOM_TOL = 1e-12, 1e-11
N4, STEPS4 = 252, 1
# the tier-1 refined-policy step gate
# (tests/test_serving.py::test_pipelined_mixed_precision_cohort_matches_f64)
ATOL, RTOL = 1e-8, 1e-7
FULL_MESH_TOL = 1e-10


def _compile_seconds():
    """A running total of JAX's trace, lowering and backend-compile time."""
    import jax

    total = [0.0]

    def listen(name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            total[0] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: total[0]


def kernel_parity() -> None:
    """The Pallas kernels on the chip against their jnp oracles."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.krylov_fused.krylov_fused import (
        fused_axpy_precond_single, spmv_dot_single)
    from repro.kernels.krylov_fused.ref import (fused_axpy_precond_ref,
                                                spmv_dot_ref)

    rng = np.random.default_rng(0)
    cases = [
        # ragged part; every band shift has a lane remainder
        (5000, 100, 10, jnp.float32, 1e-5),
        (5000, 100, 10, jnp.bfloat16, 2e-2),
        # one coarse part of the 210³ cavity (multi-block grid)
        (N * N * (N // PARTS) * ALPHA, N * N, N, jnp.float32, 1e-5),
    ]
    for m, plane, nx, dtype, tol in cases:
        offsets = (-plane, -nx, -1, 0, 1, nx, plane)
        bands = jnp.asarray(rng.standard_normal((7, m)), dtype)
        xp = jnp.asarray(rng.standard_normal(m + 2 * plane), dtype)
        kw = dict(offsets=offsets, plane=plane, accum_dtype="float32",
                  difference=True)
        y, d = spmv_dot_single(bands, xp, **kw)
        y_r, d_r = spmv_dot_ref(bands, xp, **kw)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(y_r, np.float32),
                                   rtol=tol, atol=tol)
        np.testing.assert_allclose(float(d), float(d_r), rtol=10 * tol,
                                   atol=10 * tol)
        vec = [jnp.asarray(rng.standard_normal(m), dtype) for _ in range(5)]
        alpha = jnp.asarray(0.37, jnp.float32)
        got = fused_axpy_precond_single(*vec, alpha, accum_dtype="float32")
        want = fused_axpy_precond_ref(*vec, alpha, accum_dtype="float32")
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(w, np.float32),
                                       rtol=10 * tol, atol=10 * tol)
        print(f"kernel parity {jnp.dtype(dtype).name}, {m} rows: spmv_dot "
              f"and fused_axpy_precond match ref.py (tol {tol:g})",
              flush=True)


def run_piso(mesh, label: str, clock, steps: int = STEPS, backend=None,
             **solver_kw):
    """``steps`` PISO steps, one ``run_steps`` window each; returns the
    state after every step as host arrays and the per-step p_iters.
    ``backend`` names the solve backend the pressure system must resolve
    to."""
    import jax
    import numpy as np

    from repro.fvm.piso import make_solver

    solver = make_solver("piso", mesh, alpha=ALPHA, **solver_kw)
    print(f"[{label}] {solver.describe_backends()}; p_tol {solver.p_tol:g} "
          f"mom_tol {solver.mom_tol:g} p_maxiter {solver.p_maxiter}",
          flush=True)
    got = solver.backend_for(solver.plan_p)
    if backend is not None and got != backend:
        raise SystemExit(f"[{label}] pressure backend {got!r}, expected "
                         f"{backend!r}")
    if solver.spmd_mesh is not None:
        print(f"[{label}] mesh {dict(solver.spmd_mesh.shape)} over devices "
              f"{[d.id for d in solver.spmd_mesh.devices.flat]}", flush=True)
    dt = 0.5 * mesh.h                     # the launcher's --co 0.5
    state = solver.initial_state()
    p_iters, states = [], []
    for step in range(steps):
        c0, t0 = clock(), time.perf_counter()
        state, stats = solver.run_steps(state, dt, 1)
        jax.block_until_ready(state)
        wall = time.perf_counter() - t0
        compile_s = clock() - c0
        leaves = {k: np.asarray(v) for k, v in state._asdict().items()}
        states.append(leaves)
        bad = [k for k, v in leaves.items() if not np.isfinite(v).all()]
        its = [int(i) for i in np.asarray(stats.p_iters)[0]]
        p_iters.append(its)
        print(f"[{label}] step {step}: wall {wall:.3f} s (compile "
              f"{compile_s:.1f} s of it), mom_iters "
              f"{int(stats.mom_iters[0])}, p_iters {its}, continuity "
              f"{float(stats.continuity_err[0]):.3e}", flush=True)
        if bad:
            raise SystemExit(f"[{label}] step {step}: non-finite {bad}")
        if bool(np.asarray(stats.hit_cap)[0]):
            raise SystemExit(f"[{label}] step {step}: a Krylov solve hit "
                             f"its cap (p_maxiter={solver.p_maxiter}, "
                             f"mom_maxiter={solver.mom_maxiter})")
        if not bool(np.asarray(stats.converged)[0]):
            raise SystemExit(f"[{label}] step {step}: a Krylov solve did "
                             f"not converge")
    return states, p_iters


def compare(got: dict, want: dict, atol: float, rtol: float) -> float:
    """Print per-field differences; return the worst ratio to the bound
    ``atol + rtol |want|`` (at most 1 means within tolerance)."""
    import numpy as np

    worst = 0.0
    for k in ("U", "p", "phi"):
        diff = np.abs(got[k] - want[k])
        ratio = float(np.max(diff / (atol + rtol * np.abs(want[k]))))
        print(f"  {k}: max |diff| {float(diff.max()):.3e}, max "
              f"|diff| / (atol + rtol |ref|) {ratio:.3f}", flush=True)
        worst = max(worst, ratio)
    return worst


def one_chip(clock) -> None:
    import jax

    from repro.fvm.mesh import CavityMesh

    kernel_parity()
    mesh = CavityMesh.cube(N, PARTS)
    tols = dict(p_tol=P_TOL, mom_tol=MOM_TOL)
    print(f"smoke run (not a benchmark): cavity n={N} "
          f"({mesh.n_cells_global} cells), parts={PARTS}, alpha={ALPHA}, "
          f"coarse part {mesh.n_cells * ALPHA} rows", flush=True)
    fast, _ = run_piso(mesh, "f32_ir", clock, backend="fused",
                       precision="f32_ir", **tols)
    jax.clear_caches()   # free the f32_ir executables before the f64 run
    gc.collect()
    ref, _ = run_piso(mesh, "f64", clock, REF_STEPS, backend="reference",
                      precision="f64", solver_backend="reference", **tols)
    for step in range(REF_STEPS):
        print(f"step {step}: f32_ir (fused) vs f64 (reference), "
              f"atol={ATOL:g}, rtol={RTOL:g}:", flush=True)
        if compare(fast[step], ref[step], ATOL, RTOL) > 1.0:
            raise SystemExit(f"step {step}: f32_ir differs from the f64 "
                             f"reference beyond the tier-1 step tolerance")


def four_chips(clock, devices) -> None:
    import jax

    from repro.fvm.mesh import CavityMesh

    if len(devices) < 4:
        raise SystemExit(f"--four-chips needs 4 devices, found "
                         f"{len(devices)}")
    mesh = CavityMesh.cube(N4, 4)
    print(f"smoke run (not a benchmark): cavity n={N4}, parts=4, "
          f"alpha={ALPHA}, {STEPS4} step(s): full_mesh on 4 chips vs "
          f"stacked on 1", flush=True)
    with jax.default_device(devices[0]):
        stacked, it_s = run_piso(mesh, "stacked 1 chip", clock, STEPS4,
                                 precision="f64")
    gc.collect()
    full, it_f = run_piso(mesh, "full_mesh 4 chips", clock, STEPS4,
                          solve_mode="full_mesh", precision="f64")
    stacked, full = stacked[-1], full[-1]
    print(f"p_iters stacked {it_s}, full_mesh {it_f}: "
          f"{'identical' if it_s == it_f else 'DIFFER'}; fields "
          f"(atol={FULL_MESH_TOL:g}):", flush=True)
    if compare(full, stacked, FULL_MESH_TOL, 0.0) > 1.0:
        raise SystemExit(f"full_mesh differs from stacked beyond "
                         f"{FULL_MESH_TOL:g}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip full-mesh phase")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.env import enable_compile_cache, enable_x64

    enable_x64()
    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX finds no TPU (platform "
                         f"{devices[0].platform!r}); no CPU fallback")
    print(f"devices: {len(devices)} x {devices[0].device_kind}; compile "
          f"cache {cache}", flush=True)
    clock = _compile_seconds()
    if args.four_chips:
        four_chips(clock, devices)
    else:
        one_chip(clock)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
