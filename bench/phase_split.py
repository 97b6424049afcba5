#!/usr/bin/env python3
"""Device time per phase of the PISO step, from one traced window of a cell.

    python bench/phase_split.py --workload cavity3d.repart --seed 7 \\
        --seconds 30

Builds, seeds and warms the cell as ``run.py`` does and runs the same
window with the profiler on for its first ``run.TRACE_STEPS`` steps.  Then
it lowers the compiled window on the state's shapes and sharding, with
``dt`` as the window passed it (``solver.lower_steps``: the executable that
ran, a hit in JAX's persistent cache), maps each of its instructions to
the phase that issued it and reduces the trace per phase (:mod:`phases`).

Prints one JSON object as the last line of standard output, per traced
step: the device ms of each phase of the schedule that ran (summed over
correctors) and of the ops under no phase (``other``: the seed, the
pipeline prologue and finalize scopes, and ops under no scope, each given
apart in ``outside_ms``), the busy time
(``xla.ms_per_step`` plus the Pallas kernels) and the share of it the
phases and ``other`` account for, the largest unscoped ops, and the trace
events whose instruction the map lacks (0 when the map's HLO is the
executable that ran); besides, every step's seconds (the traced ones
first), the refinement passes and inner iterations of each pressure solve
(``StepStats.p_outer``, ``p_iters``) and the seconds of the lowering.  The
correctness comparison is ``run.py``'s.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run  # noqa: E402
import spec  # noqa: E402

# the program's scopes around the phases: ``other`` holds them and the ops
# under no scope
OUTSIDE = ["seed", "prime", "finalize"]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    return ap.parse_args(argv)


def window_hlo(jax, solver, shapes, dt):
    """The HLO text of the window's executable, the seconds of lowering
    and compiling it, and whether JAX's persistent cache had to be turned
    off for it (an executable loaded from the cache may hold no HLO)."""
    def lower():
        t = time.perf_counter()
        compiled = solver.lower_steps(shapes, dt, 1)
        try:
            text = compiled.as_text() or ""
        except Exception:  # noqa: BLE001 — a loaded executable may lack it
            text = ""
        return text, time.perf_counter() - t

    text, seconds = lower()
    if text:
        return text, seconds, False
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text, seconds = lower()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    return text, seconds, True


def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.load_cell(ROOT, args.workload, bench=BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.pin_cache(ROOT)
    from repro.env import enable_compile_cache, enable_x64

    enable_x64()
    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    import phases
    import xplane
    from reference.piso import box_from

    devices = run.require_chips(jax, cell.chips)
    clock = run.CompileClock(jax)
    box = box_from(cell.config, cell.traffic["co"])
    solver = run.build(cell)
    state = run.initial_state(jax, solver, box, cell, args.seed)
    snapshot = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))
    state, _ = solver.run_steps(state, box.dt, 1)
    run.host(snapshot(state))
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        state)

    trace_dir = os.path.join(BENCH, "out", "trace",
                             f"split.{cell.name}.{args.seed}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    _, stats, ends, t_start, compiles = run.window(
        jax, solver, snapshot, state, box.dt, args.seconds, trace_dir, clock)
    del state
    text, lower_s, uncached = window_hlo(jax, solver, shapes, box.dt)
    schedule = (solver.program.pipeline.phases if solver.pipelined
                else solver.program.phases)
    names = list(dict.fromkeys(ph.name for ph in schedule))
    split = phases.reduce(trace_dir, phases.scope_map(text, names + OUTSIDE),
                          run.SPANS, module=phases.module_name(text))
    trace = xplane.reduce(trace_dir, run.SPANS)
    shutil.rmtree(trace_dir, ignore_errors=True)

    n_dev = max(trace["devices"], 1)
    kernel_s = sum(k["seconds"] for kind in trace["kernels"].values()
                   for k in kind.values()) / n_dev

    def ms(seconds):
        return 1e3 * seconds / split["steps"]

    phase_ms = {n: ms(split["phase_s"].get(n, 0.0)) for n in names}
    outside_ms = {n: ms(split["phase_s"].get(n, 0.0)) for n in OUTSIDE}
    outside_ms["unscoped"] = ms(split["other_s"])
    other_ms = sum(outside_ms.values())
    busy_ms = ms(trace["busy_s"])
    d0 = devices[0]
    out = {
        "cell": cell.name, "seed": args.seed,
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(devices)},
        "traced_steps": split["steps"],
        "phase_ms": phase_ms, "other_ms": other_ms, "outside_ms": outside_ms,
        "busy_ms": busy_ms, "xla_ms": ms(trace["xla_s"]),
        "kernel_ms": ms(kernel_s),
        "sum_over_busy": (sum(phase_ms.values()) + other_ms) / busy_ms,
        "other_share": other_ms / busy_ms,
        "idle_share": 1.0 - trace["busy_s"] / trace["window_s"],
        "unmatched": split["unmatched"],
        "other_ops": [[n, ms(s)] for n, s in split["other_ops"]],
        "step_s": [b - a for a, b in zip([0.0] + ends[:-1], ends)],
        "p_outer": [s["p_outer"].reshape(-1).tolist() for s in stats],
        "p_iters": [s["p_iters"].reshape(-1).tolist() for s in stats],
        "lower_s": lower_s, "lower_without_cache": uncached,
        "setup_s": t_start - T0, "window_compiles": compiles,
    }
    print(f"phase_split: {cell.name} seed {args.seed}: lowering the window "
          f"took {lower_s:.3f} s{' (cache off)' if uncached else ''}; "
          f"{split['unmatched']} traced events missing from the map; "
          f"phases + other = {out['sum_over_busy']:.6f} of busy",
          file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
