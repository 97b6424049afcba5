"""Fig. 7 companion: measured strong scaling, stacked vs full-mesh solve.

The paper's fig. 7 argues the repartitioned solve scales because it stops
idling the inactive-communicator ranks; our SPMD rendering of that fix is
``PisoSolver(solve_mode="full_mesh")`` — the fused pressure system is
row-sharded over BOTH mesh axes so all ``n_coarse * alpha`` devices work
during the CG loop, instead of ``alpha``-way replicating it (stacked mode,
the paper-faithful "C_i idle" layout).

This benchmark runs the real solver on 8 forced host devices and reports
the per-phase wall breakdown (assembly / update / halo / solve, from
``PisoSolver.timed_step``) for both modes at several alpha values.  The
interesting column is ``solve``: full-mesh shrinks the per-device solve
working set by alpha at the cost of boundary collective-permutes.  Host
devices serialize onto one CPU, so wall speedups here are *not* the chip
picture — the cost-model projection in fig7_strong_scaling.py covers that;
this figure validates the phase split and that both modes converge
identically (same CG iteration counts).

Each (mode, alpha) cell is a subprocess because the forced device count
must be set before JAX initializes.  CPU-only: the subprocesses force
host devices, and a child that needs a chip would find it held by the
parent, so nothing that runs on the chip calls this harness
(``chip_smoke.py --four-chips`` is the four-chip path).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit

N_DEV = 8

CODE = """
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
import jax
from repro.env import enable_x64; enable_x64()
from repro.fvm.mesh import CavityMesh
from repro.fvm.piso import PisoSolver

mode, alpha, n, steps = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), \\
    int(sys.argv[4])
solver = PisoSolver(CavityMesh.cube(n, %d), alpha=alpha, solve_mode=mode)
state = solver.initial_state()
dt = 2e-4
phases = []
iters = []
for step in range(steps):
    state, stats, ph = solver.timed_step(state, dt)
    if step > 0:  # drop the trace+compile warm-up sample
        phases.append(ph)
        iters.append([int(i) for i in stats.p_iters])
n_s = max(len(phases), 1)
agg = {k: sum(getattr(p, k) for p in phases) / n_s
       for k in ("assembly", "update", "halo", "solve")}
agg["total"] = sum(agg.values())
print(json.dumps({"mode": mode, "alpha": alpha, "phases": agg,
                  "p_iters": iters[-1] if iters else []}))
""" % (N_DEV, N_DEV)


def run(n: int = 8, alphas=(2, 4), steps: int = 4):
    env = dict(os.environ)
    env.setdefault("PYTHONPATH", "src")
    results = {}
    for alpha in alphas:
        for mode in ("stacked", "full_mesh"):
            r = subprocess.run(
                [sys.executable, "-c", CODE, mode, str(alpha), str(n),
                 str(steps)],
                capture_output=True, text=True, env=env, timeout=2400)
            tag = f"fig7fm_{mode}_alpha{alpha}"
            if r.returncode != 0:
                emit(f"{tag}_ERROR", 0.0, r.stderr.strip()[-140:])
                continue
            rec = json.loads(r.stdout.strip().splitlines()[-1])
            results[(mode, alpha)] = rec
            ph = rec["phases"]
            emit(tag, ph["total"],
                 f"as={ph['assembly']*1e3:.1f}ms up={ph['update']*1e3:.1f}ms "
                 f"ha={ph['halo']*1e3:.1f}ms so={ph['solve']*1e3:.1f}ms "
                 f"p_iters={rec['p_iters']}")
        key_s, key_f = ("stacked", alpha), ("full_mesh", alpha)
        if key_s in results and key_f in results:
            ts = results[key_s]["phases"]["solve"]
            tf = results[key_f]["phases"]["solve"]
            same = results[key_s]["p_iters"] == results[key_f]["p_iters"]
            emit(f"fig7fm_solve_ratio_alpha{alpha}", 0.0,
                 f"stacked/full_mesh solve={ts / max(tf, 1e-12):.2f}x "
                 f"iters_match={same}")
    return results


if __name__ == "__main__":
    run()
