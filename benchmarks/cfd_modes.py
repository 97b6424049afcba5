"""Beyond-paper CFD measurement: paper-faithful vs full-mesh solve layout.

Paper-faithful replicates the fused solve over the assemble axis (the SPMD
rendering of "C_i ranks skip the solve"); the full-mesh mode row-shards the
fused system over the assemble axis too.  Comparison on the production CFD
mesh (14 solve groups x alpha 15 = 210 devices): per-device solve FLOPs
should drop ~alpha x in exchange for boundary collective-permutes.
Subprocess (forced host devices).  Emits both modes' stats.  CPU-only:
a child that needs a chip would find it held by the parent, so nothing
that runs on the chip calls this harness.
"""
from __future__ import annotations

import os
import subprocess
import sys

from benchmarks.common import emit

CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json, sys
import jax
from repro.env import enable_x64; enable_x64()
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.comm import make_cfd_mesh
from repro.fvm.mesh import CavityMesh
from repro.fvm.piso import PisoSolver, PisoState
from repro.launch.dryrun import parse_collectives

full = bool(int(sys.argv[1]))
n = int(sys.argv[2])
n_solve, alpha = 14, 15
parts = n_solve * alpha
m = make_cfd_mesh(n_coarse=n_solve, alpha=alpha)
solver = PisoSolver(CavityMesh.cube(n, parts), alpha=alpha, spmd_mesh=m,
                    solve_mode="full_mesh" if full else "stacked")

def fine_sh(x):
    return NamedSharding(m, P(*((("solve", "assemble"),)
                                + (None,) * (x.ndim - 1))))

specs = jax.eval_shape(solver.initial_state)
shardings = PisoState(*[fine_sh(s) for s in specs])
args = PisoState(*[jax.ShapeDtypeStruct(s.shape, s.dtype) for s in specs])
step_fn = solver.program.as_step_fn()  # the StepProgram's fused composition
with m:
    compiled = jax.jit(step_fn,
                       in_shardings=(shardings, None)).lower(args, 1e-4).compile()
from repro.compat import cost_analysis_dict
cost = cost_analysis_dict(compiled)
mem = compiled.memory_analysis()
hlo = compiled.as_text()
col = parse_collectives(hlo)
# per-device solve working set: the DIA bands slice used inside the CG loop
# (cost_analysis counts the while body once, hiding the per-iteration win).
# In full-mesh mode the shard_map body consumes (nb, m_loc) local slices.
m_c = solver.plan_p.m_coarse
shard_rows = (f"f64[7,{m_c // alpha}]" in hlo
              or f"f64[1,7,{m_c // alpha}]" in hlo)
bands_bytes = 7 * (m_c // alpha if (full and shard_rows) else m_c) * 8
print(json.dumps({
    "mode": "full_mesh" if full else "paper_faithful",
    "flops_per_device": cost.get("flops", 0.0),
    "bytes_per_device": cost.get("bytes accessed", 0.0),
    "temp_gb": mem.temp_size_in_bytes / 1e9,
    "collective_bytes": col["total_bytes"],
    "collective_count": col["total_count"],
    "solve_bands_bytes_per_device": bands_bytes,
    "solve_rows_sharded": bool(shard_rows),
}))
"""


def run(n: int = 210):
    env = dict(os.environ)
    env.setdefault("PYTHONPATH", "src")
    import json
    out = {}
    for full in (0, 1):
        r = subprocess.run([sys.executable, "-c", CODE, str(full), str(n)],
                           capture_output=True, text=True, env=env,
                           timeout=2400)
        tag = "full_mesh" if full else "paper_faithful"
        if r.returncode == 0:
            rec = json.loads(r.stdout.strip().splitlines()[-1])
            out[tag] = rec
            emit(f"cfd_mode_{tag}_n{n}", 0.0,
                 f"solve_bands/dev={rec['solve_bands_bytes_per_device']:.3e}B "
                 f"rows_sharded={rec['solve_rows_sharded']} "
                 f"colbytes={rec['collective_bytes']:.3e} "
                 f"temp={rec['temp_gb']:.2f}GB")
        else:
            emit(f"cfd_mode_{tag}_n{n}_ERROR", 0.0,
                 r.stderr.strip()[-140:])
    if len(out) == 2:
        ratio = (out["paper_faithful"]["solve_bands_bytes_per_device"]
                 / max(out["full_mesh"]["solve_bands_bytes_per_device"], 1))
        emit(f"cfd_mode_speedup_n{n}", 0.0,
             f"per_device_solve_workingset_ratio={ratio:.1f}x (alpha=15): "
             "the solve memory/compute term drops by alpha in full-mesh mode")
    return out


if __name__ == "__main__":
    run()
