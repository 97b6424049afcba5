#!/usr/bin/env python3
"""The readings that a cell's correctness limits are set from.

    python bench/calibrate.py --workload cavity3d.repart \\
        --seeds 11,12,13 --control-seeds 3 --steps 10

One process, one build of the cell's solver.  For each seed: the seeded
initial state, the warm-up step and ``--steps`` one-step dispatches read
back as the benchmark's window reads them, then the three numbers of
:mod:`check` over every step (the program's readings).  For the first
``--control-seeds`` seeds also the control's: the reference computed in
float32, one precision below the configuration's float64, put in the
program's place on the same input states.  One JSON line per seed, each
reading with the ``correct`` that :func:`check.verdict` gives it under the
cell's limits (the program's with its failed steps, the control's as if
none failed), then the largest program reading and the smallest control
reading of each number.  The benchmark's own runs never run the control.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


def readings(jax, solver, box, cell, seed: int, steps: int,
             control: bool) -> dict:
    """The program's numbers (and, with ``control``, the control's) over
    ``steps`` window steps of one seed."""
    import jax.numpy as jnp

    import check
    import run

    state = run.initial_state(jax, solver, box, cell, seed)
    state, _ = solver.run_steps(state, box.dt, 1)
    states = [run.host(state)]
    iters, failed = [], 0
    for _ in range(steps):
        state, st = solver.run_steps(state, box.dt, 1)
        states.append(run.host(state))
        st = run.host(st)
        iters.append(int(st["p_iters"].sum()))
        failed += run.step_failed(st, states[-1])
    pairs = list(zip(states[:-1], states[1:]))
    n, parts, corr = cell.config["n"], cell.config["parts"], \
        cell.traffic["n_correctors"]
    out = {"seed": seed, "p_iters": iters, "failed": failed}
    out["program"], out["reference"] = check.compare(pairs, box, corr, n,
                                                     parts)
    out["program_correct"] = check.verdict(out["program"], cell.limits,
                                           failed)[0]
    if control:
        out["control"], _ = check.compare(pairs, box, corr, n, parts,
                                          control=jnp.float32)
        out["control_correct"] = check.verdict(out["control"], cell.limits,
                                               0)[0]
    return out


def summary(rows: list) -> dict:
    import check

    lo = {k: max(r["program"][k] for r in rows) for k in check.NUMBERS}
    ctl = [r for r in rows if "control" in r]
    hi = {k: min(r["control"][k] for r in ctl) for k in check.NUMBERS} \
        if ctl else {}
    return {"seeds": len(rows), "control_seeds": len(ctl),
            "program_max": lo, "control_min": hi}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args(argv)

    import spec
    from run import pin_cache

    cell = spec.load_cell(ROOT, args.workload)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = pin_cache(ROOT)
    from repro.env import enable_compile_cache, enable_x64

    enable_x64()
    enable_compile_cache()
    import jax

    import run
    from reference.piso import box_from

    run.require_chips(jax, cell.chips)
    box = box_from(cell.config, cell.traffic["co"])
    solver = run.build(cell)
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        rows.append(readings(jax, solver, box, cell, seed, args.steps,
                             control=i < args.control_seeds))
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps(summary(rows)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
