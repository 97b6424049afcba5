"""CFD launcher: any registered flow case under any registered program.

  python -m repro.launch.case --case cavity --program piso --n 12 --steps 10
  python -m repro.launch.case --case channel --program simple --n 8

Transient programs (PISO) advance ``--steps`` timesteps through the fused
scan-rolled stepper; with ``--adaptive`` the per-phase timers feed the
repartitioning controller, which recalibrates the cost model online and
rebinds alpha when the predicted gain clears the hysteresis threshold.
Steady programs (SIMPLE) instead iterate the program's convergence
predicate under ``lax.while_loop`` (``run_steady``), capped at
``--max-outer`` outer iterations.

``python -m repro.launch.cavity`` is a compatibility shim over this
driver with the historical defaults (``--case cavity --program piso``).
"""
from __future__ import annotations

import argparse
import time

from repro.core.controller import (ControllerConfig, PlanCache,
                                   RepartitionController)
from repro.core.cost_model import CostModel, hardware_for
from repro.env import enable_compile_cache, enable_x64
from repro.fvm.cases import case_names, get_case
from repro.fvm.mesh import CavityMesh
from repro.fvm.piso import SOLVERS, make_solver


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--case", default="cavity", choices=case_names(),
                    help="flow case (BC set) from the case registry")
    ap.add_argument("--program", default="piso",
                    choices=tuple(sorted(SOLVERS)),
                    help="timestep program: piso (transient) or simple "
                         "(steady-state outer iteration)")
    ap.add_argument("--re", type=float, default=0.0,
                    help="Reynolds number; > 0 derives --nu from the case "
                         "(nu = u_ref * L / Re at domain length L = n*h)")
    ap.add_argument("--n", type=int, default=12, help="cells per axis")
    ap.add_argument("--parts", type=int, default=4, help="fine parts (n_CPU)")
    ap.add_argument("--alpha", type=int, default=2,
                    help="repartitioning ratio (0 = pick via cost model)")
    ap.add_argument("--steps", type=int, default=10,
                    help="timesteps (transient programs)")
    ap.add_argument("--max-outer", type=int, default=0,
                    help="steady programs: outer-iteration cap "
                         "(0 = solver default)")
    ap.add_argument("--co", type=float, default=0.5, help="CFL number")
    ap.add_argument("--nu", type=float, default=0.01)
    ap.add_argument("--schedule", default="device_direct",
                    choices=["device_direct", "host_buffer"])
    ap.add_argument("--solve-mode", default="stacked",
                    choices=["stacked", "full_mesh"],
                    help="SPMD solve layout: stacked replicates solver rows "
                         "over the assemble axis (paper-faithful C_i-idle); "
                         "full_mesh row-shards the fused system over all "
                         "devices (needs --parts visible devices, e.g. "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    ap.add_argument("--solver-backend", default="auto",
                    choices=["auto", "fused", "reference"],
                    help="Krylov per-iteration backend (repro.solvers.ops): "
                         "fused = one-pass SpMV+dot and axpy-pair+Jacobi+"
                         "dots Pallas kernels; reference = the plain jnp op "
                         "sequence; auto picks fused once a part fills a "
                         "kernel row block")
    ap.add_argument("--precision", default="f64",
                    choices=["f64", "f32_ir", "bf16_ir"],
                    help="Krylov precision policy (repro.solvers.precision): "
                         "f64 solves in double precision; f32_ir/bf16_ir "
                         "run the inner sweeps at f32/bf16 storage inside "
                         "an f64 iterative-refinement loop (the Pallas TPU "
                         "kernels take no f64, so on TPU the fused backend "
                         "needs one of these)")
    ap.add_argument("--p-tol", type=float, default=1e-8,
                    help="relative tolerance of the pressure CG solves")
    ap.add_argument("--mom-tol", type=float, default=1e-7,
                    help="relative tolerance of the momentum BiCGStab "
                         "solves")
    ap.add_argument("--pipeline", default="auto",
                    choices=["auto", "on", "off"],
                    help="software-pipelined stepping (PipelinedExecutor): "
                         "auto pipelines whenever the program declares a "
                         "pipeline form (piso does; steady programs fall "
                         "back to serial), on demands it (error on steady "
                         "programs), off forces the serial fused stepper")
    ap.add_argument("--xla-tuning", action="store_true",
                    help="apply repro.env.configure_platform()'s XLA "
                         "latency-hiding/async-stream flags before backend "
                         "init (lets the GPU runtime overlap the pipelined "
                         "program's independent assembly and solve ops)")
    ap.add_argument("--adaptive", action="store_true",
                    help="feedback-driven alpha (overrides --alpha; "
                         "transient programs only)")
    ap.add_argument("--hysteresis", type=float, default=0.10,
                    help="min relative predicted gain to switch alpha")
    ap.add_argument("--sample-every", type=int, default=4,
                    help="adaptive mode: timesteps per instrumented "
                         "per-phase sample; steps in between advance via "
                         "the fused scan-rolled stepper (one XLA dispatch "
                         "per stretch)")
    ap.add_argument("--scan-steps", type=int, default=8,
                    help="scan-roll window: up to this many timesteps "
                         "execute as ONE XLA dispatch (StepProgram fused "
                         "executor) — the whole run in non-adaptive mode, "
                         "and the rolled stretches between instrumented "
                         "samples in adaptive mode")
    return ap


def solve_health(stats, j=None) -> str:
    """``ok``, ``CAPPED``, ``DIVERGED`` or ``UNCONVERGED``: the Krylov
    health flags of one step's ``StepStats`` (step ``j`` of a window)."""
    def flag(name):
        v = getattr(stats, name)
        return bool(v if j is None else v[j])

    if flag("diverged"):
        return "DIVERGED"
    if flag("hit_cap"):
        return "CAPPED"
    return "ok" if flag("converged") else "UNCONVERGED"


def fail_on_unhealthy(bad: list[str], solver) -> None:
    """Exit non-zero, naming the steps, when any Krylov solve failed: a
    capped or diverged solve must never pass for a result."""
    if bad:
        raise SystemExit(
            f"FAILED: Krylov solves failed at {', '.join(bad)} "
            f"(p_maxiter={solver.p_maxiter}, "
            f"mom_maxiter={solver.mom_maxiter})")


def run_steady(args, mesh, alpha, nu) -> None:
    """Steady program: iterate to the program's convergence predicate."""
    solver = make_solver(args.program, mesh, alpha=alpha, nu=nu,
                         case=args.case, update_schedule=args.schedule,
                         solve_mode=args.solve_mode,
                         solver_backend=args.solver_backend,
                         pipeline=args.pipeline,
                         precision=args.precision, p_tol=args.p_tol,
                         mom_tol=args.mom_tol)
    print(solver.describe_backends())
    dt = args.co * mesh.h  # ignored by steady assembly; kept for the ABI
    cap = args.max_outer or None
    t0 = time.time()
    state, stats, n_outer = solver.run_steady(dt=dt, max_outer=cap)
    wall = time.time() - t0
    n_outer = int(n_outer)
    cont = float(stats.continuity_err)
    u_delta = float(stats.u_delta)
    done = bool(solver.program.converged(stats))
    print(f"{args.case}/{args.program}: {'converged' if done else 'CAPPED'} "
          f"after {n_outer} outer iterations in {wall:.2f}s "
          f"({wall / max(n_outer, 1) * 1e3:.1f} ms/outer)")
    print(f"  continuity={cont:.2e} (tol {solver.tol_continuity:.0e}) "
          f"u_delta={u_delta:.2e} (tol {solver.tol_u:.0e}) "
          f"mom_iters={int(stats.mom_iters)} "
          f"p_iters={[int(i) for i in stats.p_iters]} "
          f"solves={solve_health(stats)}")
    print(f"  ({mesh.n_cells_global} cells, alpha={solver.alpha}, "
          f"relax_u={solver.relax_u}, relax_p={solver.relax_p}, "
          f"solve_mode={args.solve_mode}, "
          f"solver_backend={args.solver_backend})")
    health = solve_health(stats)
    fail_on_unhealthy([] if health == "ok" else [f"outer {n_outer}: {health}"],
                      solver)


def run_transient(args, mesh, alpha, nu, cm) -> None:
    """Transient program: scan-rolled timestepping, optionally adaptive."""
    from repro.fvm.step_program import get_program, roll_schedule

    dt = args.co * mesh.h  # u_ref 1 -> dt = Co*h
    # resolve the pipeline knob once, the same way the solver will: the
    # controller/cost-model alpha picks then score the overlap objective
    pipelined = (args.pipeline == "on"
                 or (args.pipeline == "auto"
                     and get_program(args.program).pipelined))

    if args.adaptive:
        cache = PlanCache()
        # fixed_fine feasibility keeps only divisors of --parts
        cfg = ControllerConfig(hysteresis=args.hysteresis,
                               sample_every=max(args.sample_every, 1))
        ctl = RepartitionController(cm, n_cpu=args.parts, n_gpu=1,
                                    alpha0=alpha, config=cfg, cache=cache,
                                    fixed_fine=True,
                                    solve_mode=args.solve_mode,
                                    solver_backend=args.solver_backend,
                                    pipelined=pipelined)
        solver = make_solver(args.program, mesh, alpha=ctl.alpha, nu=nu,
                             case=args.case, update_schedule=args.schedule,
                             plan_cache=cache, solve_mode=args.solve_mode,
                             solver_backend=args.solver_backend,
                             pipeline=args.pipeline,
                             precision=args.precision, p_tol=args.p_tol,
                             mom_tol=args.mom_tol)
        print(solver.describe_backends())
        print(f"controller start: alpha={ctl.alpha} "
              f"solve_mode={args.solve_mode} "
              f"solver_backend={args.solver_backend} "
              f"pipeline={args.pipeline} (resolved {solver.pipelined}) "
              f"sample_every={cfg.sample_every}")
        state = solver.initial_state()
        t0 = time.time()
        step = 0
        bad = []
        # same cadence driver as SimulationEngine.step_session: sample the
        # instrumented walk on the anchored grid, scan-roll the stretches
        for is_sample, chunk in roll_schedule(0, args.steps,
                                              cfg.sample_every,
                                              cap=max(args.scan_steps, 1)):
            if is_sample:
                # instrumented sample: per-phase timers feed the controller
                state, stats, sample = solver.timed_step(state, dt)
                if solve_health(stats) != "ok":
                    bad.append(f"step {step}: {solve_health(stats)}")
                new_alpha = ctl.step(sample)
                if new_alpha != solver.alpha:
                    print(f"step {step}: controller switch alpha "
                          f"{solver.alpha} -> {new_alpha}")
                    solver.rebind_alpha(new_alpha)
                print(f"step {step}: alpha={solver.alpha} "
                      f"p_iters={[int(i) for i in stats.p_iters]} "
                      f"continuity={float(stats.continuity_err):.2e} "
                      f"phases(ms)=[as {sample.assembly*1e3:.1f} "
                      f"up {sample.update*1e3:.1f} ha {sample.halo*1e3:.1f} "
                      f"so {sample.solve*1e3:.1f}]")
            else:
                # fused scan-rolled stretch: ONE XLA dispatch
                state, window = solver.run_steps(state, dt, chunk)
                bad += [f"step {step + j}: {solve_health(window, j)}"
                        for j in range(chunk)
                        if solve_health(window, j) != "ok"]
                print(f"steps {step}..{step + chunk - 1}: "
                      f"alpha={solver.alpha} rolled x{chunk} "
                      f"p_iters={[int(i) for i in window.p_iters[-1]]} "
                      f"continuity={float(window.continuity_err[-1]):.2e}")
            step += chunk
        s = ctl.stats()
        print(f"{args.steps} steps in {time.time() - t0:.2f}s "
              f"({mesh.n_cells_global} cells); final alpha={ctl.alpha}, "
              f"{len(s['switches'])} switch(es), "
              f"plan cache {s['cache']['hits']} hits / "
              f"{s['cache']['misses']} misses")
        fail_on_unhealthy(bad, solver)
        return

    if alpha is None:
        alpha = cm.optimal_alpha(n_cpu=args.parts, n_gpu=1,
                                 pipelined=pipelined)
        print(f"cost model picked alpha={alpha}"
              + (" (overlap objective)" if pipelined else ""))
    solver = make_solver(args.program, mesh, alpha=alpha, nu=nu,
                         case=args.case, update_schedule=args.schedule,
                         solve_mode=args.solve_mode,
                         solver_backend=args.solver_backend,
                         pipeline=args.pipeline,
                         precision=args.precision, p_tol=args.p_tol,
                         mom_tol=args.mom_tol)
    print(solver.describe_backends())
    state = solver.initial_state()
    t0 = time.time()
    scan = max(args.scan_steps, 1)
    step = 0
    bad = []
    # every=None: no sampling — pure scan-rolled windows of <= scan steps
    for _sample, chunk in roll_schedule(0, args.steps, None, cap=scan):
        # each window is ONE XLA dispatch; stats come back per-step stacked
        state, stats = solver.run_steps(state, dt, chunk)
        for j in range(chunk):
            health = solve_health(stats, j)
            print(f"step {step + j}: mom_iters={int(stats.mom_iters[j])} "
                  f"p_iters={[int(i) for i in stats.p_iters[j]]} "
                  f"continuity={float(stats.continuity_err[j]):.2e} "
                  f"solves={health}")
            if health != "ok":
                bad.append(f"step {step + j}: {health}")
        step += chunk
    print(f"{args.steps} steps in {time.time() - t0:.2f}s "
          f"({mesh.n_cells_global} cells, alpha={alpha}, "
          f"solve_mode={args.solve_mode}, "
          f"solver_backend={args.solver_backend}, "
          f"pipeline={args.pipeline} (resolved {solver.pipelined}), "
          f"scan_steps={scan})")
    fail_on_unhealthy(bad, solver)


def main(argv=None):
    args = build_parser().parse_args(argv)

    if args.xla_tuning:
        # must precede backend init (the jax import the modules above
        # pull in is fine — XLA reads the env on first backend *use*,
        # not on import)
        from repro.env import configure_platform

        configure_platform()
    enable_x64()
    enable_compile_cache()
    import jax

    # resolve "auto" at the fine part size — the smallest solve part any
    # alpha produces, so the cost model's fused bytes/iter prior flips
    # only when every candidate alpha runs the fused kernels (larger
    # alphas fuse parts of alpha * this size and may go fused earlier;
    # same conservative convention as RepartitionController)
    from repro.solvers.ops import resolve_backend

    eff_backend = resolve_backend(args.solver_backend,
                                  args.n ** 3 // args.parts,
                                  precision=args.precision)
    cm = CostModel(hardware_for(jax.devices()[0]), n_dofs=args.n ** 3,
                   fused_solver=eff_backend == "fused"
                   ).with_precision(args.precision)
    alpha = args.alpha
    if alpha == 0 or args.adaptive:
        alpha = None  # let the controller/cost model pick

    mesh = CavityMesh.cube(args.n, args.parts)
    nu = args.nu
    if args.re > 0:
        case = get_case(args.case, reynolds=args.re)
        nu = case.nu(args.n * mesh.h)
        print(f"Re={args.re:g}: derived nu={nu:.3e} "
              f"(u_ref={case.u_ref:g}, L={args.n * mesh.h:g})")

    from repro.fvm.step_program import get_program

    if not get_program(args.program).transient:
        if args.adaptive:
            print("note: --adaptive applies to transient programs only; "
                  "running the steady outer loop at the fixed alpha")
        if alpha is None:
            alpha = cm.optimal_alpha(n_cpu=args.parts, n_gpu=1)
            print(f"cost model picked alpha={alpha}")
        run_steady(args, mesh, alpha, nu)
        return
    run_transient(args, mesh, alpha, nu, cm)


if __name__ == "__main__":
    main()
