#!/usr/bin/env python3
"""Benchmark of the repartitioned PISO solver: one cell, one run, one process.

    python bench/run.py --workload cavity3d.repart --seed 7 --seconds 51 \\
        --trace 0

The cell (``--workload``) is an entry of ``BENCHMARK.json`` at the
checkout's root; its configuration, traffic, metric readers and limits are
files under ``bench/`` (:mod:`spec`).  The run

1. builds the solver through the launcher's path (``make_solver``),
2. makes the initial state on the device from ``--seed``: the fluid at rest
   plus a random velocity perturbation of the traffic's amplitude,
3. warms up the window's executables, the step and the state's copy, with
   one ``run_steps(state, dt, 1)`` window (their compilation, or their load
   from JAX's persistent cache, and one step),
4. times one-step ``run_steps`` windows, each ending in
   ``block_until_ready``, until a step ends ``--seconds`` after the first
   was dispatched.  Each step's output is copied on the device, queued
   behind the step, before the next step takes the state's buffers; the
   host reads that copy back while the device runs the next step,
5. runs the plain reference (``bench/reference``) over every step of the
   window from the program's own input states and holds the outputs
   against it (:mod:`check`); a step whose solves capped, diverged or did
   not converge, or that left a field not finite, fails the run too,
6. prints each number compared beside its limit as the last lines of
   standard error, and one JSON object as the last line of standard output.

``--trace 1`` records the profiler's trace of the first ``TRACE_STEPS``
steps of the window and reports the per-layer metrics instead of the
end-to-end ones.  A host without a TPU, or with fewer chips than the cell
asks for, is refused: no CPU fallback, no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import spec  # noqa: E402

SPANS = ("bench.dispatch", "bench.wait", "bench.readback")
CACHE = ".jax_cache"
# steps of the window that ``--trace 1`` records
TRACE_STEPS = 2


def pin_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path of the checkout,
    whatever the host sets: only the first run of a cell there compiles,
    and two checkouts never share one.  Set before JAX is imported.  The
    cache is unbounded: with a size limit JAX's eviction looks for an
    access-time file beside every entry and, where one is missing, stores
    nothing."""
    path = os.path.join(root, CACHE)
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    return path


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(jax, chips: int):
    """The TPU devices of this host; exits when there are none or fewer
    than ``chips``."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: JAX finds no TPU (platform "
                         f"{devices[0].platform!r}); no CPU fallback")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX finds "
                         f"{len(devices)}")
    return devices


class CompileClock:
    """Seconds and events of JAX's tracing, lowering and compilation."""

    def __init__(self, jax):
        self.seconds, self.events = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs
            self.events += 1


def build(cell):
    """The solver of the cell, built as the launcher builds it."""
    from repro.fvm.mesh import CavityMesh
    from repro.fvm.piso import make_solver

    cfg, tr = cell.config, cell.traffic
    mesh = CavityMesh.cube(cfg["n"], cfg["parts"], length=cfg["length"])
    return make_solver(
        "piso", mesh, alpha=tr["alpha"], case=cfg["case"],
        nu=cfg["u_ref"] * cfg["length"] / cfg["reynolds"],
        n_correctors=tr["n_correctors"], precision=tr["precision"],
        solver_backend=tr["solver_backend"], pipeline=tr["pipeline"],
        p_tol=tr["p_tol"], mom_tol=tr["mom_tol"])


def initial_state(jax, solver, box, cell, seed: int):
    """The seeded initial state in the program's layout, made on the
    device in one jitted call: ``U`` a normal perturbation of the
    traffic's amplitude, ``p`` and the interior fluxes 0, the z boundary
    fluxes those of the case (:func:`reference.piso.boundary_flux`)."""
    import jax.numpy as jnp
    from reference.piso import boundary_flux

    n, parts = cell.config["n"], cell.config["parts"]
    amp = float(cell.traffic["perturbation"])
    zero = solver.initial_state()

    @jax.jit
    def make(key):
        U = amp * jax.random.normal(key, (n, n, n, 3), zero.U.dtype)
        fb = boundary_flux(box, U).reshape(2, n * n)
        phi_b = (jnp.zeros_like(zero.phi_b).at[0, 0].set(fb[0])
                 .at[parts - 1, 1].set(fb[1]))
        return U.reshape(zero.U.shape), phi_b

    U, phi_b = make(jax.random.key(seed))
    return zero._replace(U=U, phi_b=phi_b)


def host(tree) -> dict:
    import numpy as np

    return {k: np.asarray(v) for k, v in tree._asdict().items()}


def step_failed(stats: dict, state: dict) -> bool:
    import numpy as np

    ok = bool(stats["converged"].all()) and not bool(stats["hit_cap"].any())
    ok = ok and not bool(stats["diverged"].any())
    return not (ok and all(np.isfinite(v).all() for v in state.values()))


def window(jax, solver, snapshot, state, dt: float, seconds: float,
           trace_dir: str | None, clock: CompileClock):
    """The measured window: one-step dispatches until a step ends
    ``seconds`` after the first was dispatched.  Returns the host states
    after each step, the steps' stats, their end times and the window's
    start (host clock).

    The input state is donated to the next step, so each output is copied
    on the device by ``snapshot`` (one small op behind the step) and that
    copy goes to the host while the device runs the next step: the
    read-back does not hold the device idle."""
    states, stats, ends = [], [], []
    pending = None
    compiles = clock.events
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    t_start = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation(SPANS[0]):
            state, st = solver.run_steps(state, dt, 1)
            copy = snapshot(state)
            for leaf in jax.tree.leaves(copy):
                leaf.copy_to_host_async()
        if pending is not None:
            with jax.profiler.TraceAnnotation(SPANS[2]):
                states.append(host(pending))
        with jax.profiler.TraceAnnotation(SPANS[1]):
            jax.block_until_ready((state, st))
        ends.append(time.perf_counter() - t_start)
        stats.append(st)
        pending = copy
        if trace_dir and len(ends) == TRACE_STEPS:
            jax.profiler.stop_trace()
            trace_dir = None
        if ends[-1] >= seconds:
            break
    if trace_dir:
        jax.profiler.stop_trace()
    states.append(host(pending))
    return (states, [host(st) for st in stats], ends, t_start,
            clock.events - compiles)


def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.load_cell(ROOT, args.workload, bench=BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = pin_cache(ROOT)
    from repro.env import enable_compile_cache, enable_x64

    enable_x64()
    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    import check
    from reference.piso import box_from

    devices = require_chips(jax, cell.chips)
    clock = CompileClock(jax)
    cfg, tr = cell.config, cell.traffic
    box = box_from(cfg, tr["co"])

    t = time.perf_counter()
    solver = build(cell)
    plan_s = time.perf_counter() - t
    state = initial_state(jax, solver, box, cell, args.seed)
    # warm-up: the window's own executables (the step and the state's
    # copy), compiled or loaded, and a step
    snapshot = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))
    state, _ = solver.run_steps(state, box.dt, 1)
    first = host(snapshot(state))

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(BENCH, "out", "trace",
                                 f"{cell.name}.{args.seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    states, stats, ends, t_start, window_compiles = window(
        jax, solver, snapshot, state, box.dt, args.seconds, trace_dir, clock)
    setup_s = t_start - T0
    setup_compile_s = clock.seconds  # before the reference compiles
    del state
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell.chips])
    del solver
    gc.collect()

    # the comparison, over every step of the window
    numbers, ref_report = check.compare(
        zip([first] + states[:-1], states), box, tr["n_correctors"],
        cfg["n"], cfg["parts"])
    failed = sum(step_failed(s, x) for s, x in zip(stats, states))
    correct, checks = check.verdict(numbers, cell.limits, failed)

    reduced = None
    if args.trace:
        import xplane

        reduced = xplane.reduce(trace_dir, SPANS)
        shutil.rmtree(trace_dir, ignore_errors=True)

    d0 = devices[0]
    run = {
        "cell": cell,
        "setup": {"total_s": setup_s, "plan_s": plan_s,
                  "compile_s": setup_compile_s},
        "window": {"ends": ends,
                   "p_iters": [s["p_iters"].sum() for s in stats],
                   "mom_iters": [s["mom_iters"].sum() for s in stats]},
        "device": {"peak_bytes": peak},
        "trace": reduced,
    }
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = spec.load_reader(m["name"], bench=BENCH)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": len(stats),
              "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["top_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks

    steps = [round(b - a, 4) for a, b in zip([0.0] + ends[:-1], ends)]
    print(f"bench: {cell.name} seed {args.seed}: {len(stats)} steps in "
          f"{ends[-1]:.3f} s {steps}, setup {setup_s:.3f} s, "
          f"{window_compiles} compile events in the window; reference "
          f"{ref_report}",
          file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
