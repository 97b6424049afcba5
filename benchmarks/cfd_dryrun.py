"""CFD production dry-run: lower the PISO step on the production CFD mesh.

Proves the paper's own workload shards at cluster scale, matching the
paper's multilevel decomposition n_total = n_nodes x n_GPUs x alpha:

* single-pod: 210 fine parts = 14 solve groups x alpha 15  (420^3 grid)
* multi-pod:  420 fine parts = 28 solve groups x alpha 15  (2 pods)

Runs in a subprocess (needs forced host devices before jax import).  Emits
memory/cost/collective stats like launch/dryrun and appends JSONs to
results/dryrun/cfd_*.json.  CPU-only: a child that needs a chip would find
it held by the parent, so nothing that runs on the chip calls this
harness.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import emit

CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json, sys
import jax
from repro.env import enable_x64; enable_x64()
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.comm import make_cfd_mesh
from repro.fvm.mesh import CavityMesh
from repro.fvm.piso import PisoSolver, PisoState
from repro.launch.dryrun import parse_collectives

multi = bool(int(sys.argv[1]))
n = int(sys.argv[2])            # cells per axis (must divide parts)
n_solve = 28 if multi else 14   # paper: n_nodes x 4 GPUs
alpha = 15
parts = n_solve * alpha

mesh_cfd = CavityMesh.cube(n, parts)
solver = PisoSolver(mesh_cfd, alpha=alpha)
m = make_cfd_mesh(n_coarse=n_solve, alpha=alpha)

def fine_sh(x):
    return NamedSharding(m, P(*((("solve", "assemble"),)
                                + (None,) * (x.ndim - 1))))

specs = jax.eval_shape(solver.initial_state)
shardings = PisoState(*[fine_sh(s) for s in specs])
arg_specs = PisoState(*[jax.ShapeDtypeStruct(s.shape, s.dtype)
                        for s in specs])

step_fn = solver.program.as_step_fn()  # the StepProgram's fused composition
with m:
    lowered = jax.jit(step_fn,
                      in_shardings=(shardings, None)).lower(arg_specs, 1e-4)
    compiled = lowered.compile()
mem = compiled.memory_analysis()
from repro.compat import cost_analysis_dict
cost = cost_analysis_dict(compiled)
rec = {
    "arch": "cfd-lidDrivenCavity3D", "shape": f"n{n}_alpha{alpha}",
    "mesh": "multi_pod" if multi else "single_pod", "status": "ok",
    "n_devices": parts,
    "argument_size_in_bytes": int(mem.argument_size_in_bytes),
    "temp_size_in_bytes": int(mem.temp_size_in_bytes),
    "flops_per_device": float(cost.get("flops", 0.0)),
    "bytes_per_device": float(cost.get("bytes accessed", 0.0)),
    "collectives": parse_collectives(compiled.as_text()),
}
os.makedirs("results/dryrun", exist_ok=True)
name = f"cfd__{rec['shape']}__{rec['mesh']}"
with open(f"results/dryrun/{name}.json", "w") as f:
    json.dump(rec, f, indent=2)
print(json.dumps({k: rec[k] for k in ("shape", "mesh", "n_devices",
                                      "temp_size_in_bytes",
                                      "flops_per_device")}))
print("collective_bytes", rec["collectives"]["total_bytes"])
"""


def run(sizes=(210,), multi_pod_sizes=(420,)):
    env = dict(os.environ)
    env.setdefault("PYTHONPATH", "src")
    for multi, sizes_ in ((0, sizes), (1, multi_pod_sizes)):
        for n in sizes_:
            r = subprocess.run(
                [sys.executable, "-c", CODE, str(multi), str(n)],
                capture_output=True, text=True, env=env, timeout=2400)
            tag = f"cfd_dryrun_n{n}_{'multi' if multi else 'single'}"
            if r.returncode == 0:
                lines = r.stdout.strip().splitlines()
                emit(tag, 0.0, lines[-2][:100])
            else:
                emit(tag + "_ERROR", 0.0, r.stderr.strip()[-150:])


if __name__ == "__main__":
    run()
