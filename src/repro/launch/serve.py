"""Serving launcher: prefill → (optional alpha-fusion KV repartition) →
batched greedy decode.  CPU demo with smoke configs:

  python -m repro.launch.serve --arch qwen3-0.6b --smoke --n-new 16

``--sessions N`` switches to the CFD solver-as-a-service driver instead:
N concurrent PISO tenants (mixed timestep sizes) advance through the
engine's cohort-batched ``step_all`` — same-shape sessions stack into
cohorts and a rolled window of the whole cohort is ONE XLA dispatch
(``repro.serving.engine.SimulationEngine``):

  python -m repro.launch.serve --sessions 8 --steps 32 --cfd-n 8 --parts 4
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config, get_smoke_config
from repro.env import enable_compile_cache, enable_x64
from repro.models import lm
from repro.serving.engine import generate


def mesh_mix(args):
    """The heterogeneous tenant mix: meshes sharing one per-part slab
    structure (nx = ny = cfd_n, nzl = cfd_n // parts) with slab counts
    {parts/2 .. parts} — exactly what size-class padding co-batches.
    ``--cases``/``--programs`` widen the mix along the other two tenant
    axes: arrivals sample a flow case and a timestep program per tenant,
    so the scheduler faces genuinely heterogeneous cohort keys."""
    from repro.fvm.mesh import CavityMesh

    nzl = args.cfd_n // args.parts
    parts = sorted({max(2, args.parts // 2), max(2, 3 * args.parts // 4),
                    args.parts})
    return [CavityMesh(nx=args.cfd_n, ny=args.cfd_n, nz=nzl * p,
                       n_parts=p, h=0.1 / args.cfd_n) for p in parts]


def _tenant_axes(args) -> tuple[list[str], list[str]]:
    """Validated (cases, programs) sampling lists from the CLI."""
    from repro.fvm.cases import case_names
    from repro.fvm.piso import SOLVERS

    cases = [c.strip() for c in args.cases.split(",") if c.strip()]
    programs = [p.strip() for p in args.programs.split(",") if p.strip()]
    bad = sorted(set(cases) - set(case_names()))
    if bad:
        raise SystemExit(f"unknown case(s) {bad} (registered: "
                         f"{case_names()})")
    bad = sorted(set(programs) - set(SOLVERS))
    if bad:
        raise SystemExit(f"unknown program(s) {bad} (registered: "
                         f"{tuple(sorted(SOLVERS))})")
    return cases, programs


def serve_cfd_arrivals(args) -> dict:
    """Open-loop serving: Poisson arrivals of a heterogeneous tenant mix
    scheduled by :class:`~repro.serving.scheduler.EngineScheduler` —
    size-class cohorts, deadline preemption, per-class p50/p99."""
    enable_x64()
    from repro.core.controller import ControllerConfig
    from repro.serving.engine import SimulationEngine
    from repro.serving.scheduler import (BULK, DEADLINE, EngineScheduler,
                                         SessionSpec)

    cfg = ControllerConfig(sample_every=max(args.sample_every, 1))
    eng = SimulationEngine(config=cfg, scan_window=max(args.scan_steps, 1),
                           lane_classes=args.lane_classes,
                           track_latency=True)
    sched = EngineScheduler(eng, max_wait_rounds=args.max_wait_rounds)
    rng = np.random.default_rng(args.seed)
    meshes = mesh_mix(args)
    cases, programs = _tenant_axes(args)
    t = 0.0
    for i in range(args.sessions):
        t += float(rng.exponential(1.0 / args.arrival_rate))
        mesh = meshes[int(rng.integers(len(meshes)))]
        deadline = float(rng.random()) < args.deadline_frac
        sched.submit(SessionSpec(
            sid=f"tenant{i}", mesh=mesh, dt=args.co * mesh.h,
            n_steps=args.steps, arrival_t=t,
            priority=DEADLINE if deadline else BULK,
            deadline_ms=args.deadline_ms if deadline else None,
            open_kwargs={"adaptive": args.adaptive,
                         "alpha0": args.alpha or None, "nu": args.nu,
                         "solver_backend": args.solver_backend,
                         "pipeline": args.pipeline,
                         "program": programs[int(rng.integers(len(programs)))],
                         "case": cases[int(rng.integers(len(cases)))]}))
    t0 = time.time()
    rounds = sched.run()
    wall = time.time() - t0
    stats = sched.stats()
    done = args.sessions * args.steps
    print(f"served {args.sessions} arrivals ({done} session-steps) in "
          f"{rounds} rounds / {wall:.2f}s ({done / wall:.1f} steps/s), "
          f"{stats['dispatches']} dispatches")
    for prio, row in sorted(stats["latency"]["classes"].items()):
        print(f"  {prio}: n={row['n']} p50={row['p50'] * 1e3:.2f}ms "
              f"p99={row['p99'] * 1e3:.2f}ms")
    print(f"engine counters: {stats['engine']['counters']}")
    return stats


def _state_digest(eng) -> dict:
    """Parse-exact per-session state digests (sha256 over raw leaf bytes)
    — the kill-and-resume parity gate compares these across runs."""
    import hashlib

    out = {}
    for sid in sorted(eng.sessions):
        h = hashlib.sha256()
        for leaf in eng.sessions[sid].state:
            h.update(np.asarray(leaf).tobytes())
        out[sid] = h.hexdigest()[:16]
    return out


def serve_cfd_supervised(args) -> None:
    """Supervised/chaos/checkpointed CFD serving (the correctness driver).

    Windows of ``--scan-steps`` advance every session toward ``--steps``
    **total** steps each; the :class:`~repro.faults.ChaosMonkey` pokes its
    seeded fault schedule between windows; ``--snapshot-dir`` checkpoints
    the engine (at ``--snapshot-every`` boundaries and at the end) and
    ``--resume`` restores from it.  The ``digest`` lines printed at the
    end are byte-exact state hashes: a killed run resumed from its
    snapshot must reproduce the uninterrupted run's digests bit-for-bit
    (the CI chaos-smoke job asserts exactly that).
    """
    enable_x64()
    from repro.core.controller import ControllerConfig
    from repro.faults import ChaosMonkey, parse_kinds
    from repro.fvm.mesh import CavityMesh
    from repro.serving.engine import SimulationEngine
    from repro.serving.supervisor import SupervisorConfig

    if args.resume:
        if not args.snapshot_dir:
            raise SystemExit("--resume needs --snapshot-dir")
        eng = SimulationEngine.restore(args.snapshot_dir)
        print(f"resumed {len(eng.sessions)} sessions from "
              f"{args.snapshot_dir} at steps "
              f"{sorted({s.steps_done for s in eng.sessions.values()})}")
    else:
        cfg = ControllerConfig(sample_every=max(args.sample_every, 1))
        sup_cfg = SupervisorConfig(
            fallback_backend=args.fallback_backend or None)
        mesh = CavityMesh.cube(args.cfd_n, args.parts)
        eng = SimulationEngine(config=cfg,
                               scan_window=max(args.scan_steps, 1),
                               supervise=True, supervisor_config=sup_cfg)
        base_dt = args.co * mesh.h
        for i in range(args.sessions):
            eng.open_session(f"tenant{i}", mesh, dt=base_dt * (1 + 0.1 * i),
                             alpha0=args.alpha or None, nu=args.nu,
                             adaptive=args.adaptive,
                             solver_backend=args.solver_backend,
                             pipeline=args.pipeline)
        print(f"opened {args.sessions} supervised sessions, cohorts="
              f"{[len(g) for g in eng.cohorts().values()]}")

    chaos = None
    if args.chaos is not None:
        seed = args.seed if args.chaos_seed is None else args.chaos_seed
        chaos = ChaosMonkey(seed, sorted(eng.sessions),
                            kinds=parse_kinds(args.chaos),
                            n_events=args.chaos_events or None,
                            horizon=max(2, args.steps))
        print("chaos schedule:",
              [(e.step, e.sid, e.kind) for e in chaos.events])

    window = max(args.scan_steps, 1)
    next_snap = args.snapshot_every or 0
    while True:
        live = [s for s in eng.sessions.values()
                if s.steps_done < args.steps]
        if not live:
            break
        n = min([window] + [args.steps - s.steps_done for s in live])
        eng.step_all(n, sids=[s.sid for s in live])
        if chaos is not None:
            for ev in chaos.poke(eng):
                print(f"chaos: injected {ev.kind} into {ev.sid} "
                      f"(scheduled step {ev.step})")
        if (args.snapshot_dir and next_snap and eng.sessions
                and min(s.steps_done for s in eng.sessions.values())
                >= next_snap):
            eng.snapshot(args.snapshot_dir)
            print(f"snapshot @ step {next_snap} -> {args.snapshot_dir}")
            next_snap += args.snapshot_every
    if args.snapshot_dir:
        eng.snapshot(args.snapshot_dir)
        print(f"snapshot -> {args.snapshot_dir}")

    counts = {"healthy": 0, "degraded": 0, "quarantined": 0,
              "failed": len(eng.failed)}
    for s in eng.sessions.values():
        counts[s.supervisor.state] += 1
    print("supervision:", " ".join(f"{k}={v}" for k, v in counts.items()))
    for sid, s in sorted(eng.sessions.items()):
        print(f"health {sid} {s.supervisor.state} steps={s.steps_done} "
              f"events={len(s.supervisor.events)}")
    for sid in sorted(eng.failed):
        print(f"health {sid} failed "
              f"events={len(eng.failed[sid]['events'])}")
    for sid, h in _state_digest(eng).items():
        print(f"digest {sid} {h}")
    print(f"counters: {eng.stats()['counters']}")


def serve_cfd(args) -> None:
    """Multi-tenant PISO serving: cohort-batched stepping of N sessions."""
    enable_x64()
    from repro.core.controller import ControllerConfig
    from repro.fvm.mesh import CavityMesh
    from repro.serving.engine import SimulationEngine

    mesh = CavityMesh.cube(args.cfd_n, args.parts)
    cfg = ControllerConfig(sample_every=max(args.sample_every, 1))
    steps = args.steps
    if args.adaptive and steps % cfg.sample_every:
        # the warm-up request below only compiles the timed request's
        # window lengths when both start on the same sampling phase, i.e.
        # when steps is a multiple of the cadence
        steps += cfg.sample_every - steps % cfg.sample_every
        print(f"note: rounding --steps up to {steps} (a multiple of "
              f"--sample-every {cfg.sample_every}) so the compile warm-up "
              f"covers the timed request's window lengths")
    eng = SimulationEngine(config=cfg, scan_window=max(args.scan_steps, 1))
    base_dt = args.co * mesh.h
    for i in range(args.sessions):
        # mixed timestep sizes: dt is a traced per-session vector in the
        # batched program, so the spread costs no extra compilation
        eng.open_session(f"tenant{i}", mesh, dt=base_dt * (1 + 0.1 * i),
                         alpha0=args.alpha or None, nu=args.nu,
                         adaptive=args.adaptive,
                         solver_backend=args.solver_backend,
                         pipeline=args.pipeline)
    print(f"opened {args.sessions} sessions, cohorts="
          f"{[len(g) for g in eng.cohorts().values()]}")

    # compile warm-up outside the timed window: one full request compiles
    # the same rolled window lengths (the non-adaptive chunking does not
    # depend on the start step; an adaptive request re-aligns to the same
    # sampling phase because steps is a cadence multiple, enforced above)
    eng.step_all(steps)
    t0 = time.time()
    eng.step_all(steps)
    wall = time.time() - t0
    stats = eng.stats()
    done = args.sessions * steps
    print(f"advanced {done} session-steps in {wall:.2f}s "
          f"({done / wall:.1f} steps/s)")
    print(f"counters: {stats['counters']}")
    print(json.dumps(stats["sessions"], indent=2))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="LM architecture (LM serving mode)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--n-new", type=int, default=16)
    # -- CFD multi-tenant mode (--sessions N) ------------------------------
    ap.add_argument("--sessions", type=int, default=0,
                    help="open N concurrent PISO sessions and advance them "
                         "via cohort-batched step_all (CFD serving mode)")
    ap.add_argument("--steps", type=int, default=16,
                    help="timesteps to advance every session")
    ap.add_argument("--cfd-n", type=int, default=8,
                    help="cavity cells per axis (CFD mode)")
    ap.add_argument("--parts", type=int, default=4)
    ap.add_argument("--alpha", type=int, default=2,
                    help="repartitioning ratio (0 = cost-model pick)")
    ap.add_argument("--nu", type=float, default=0.01)
    ap.add_argument("--co", type=float, default=0.5, help="CFL number")
    ap.add_argument("--adaptive", action="store_true",
                    help="per-session adaptive controllers (sampled "
                         "instrumented steps feed each tenant's controller)")
    ap.add_argument("--sample-every", type=int, default=4)
    ap.add_argument("--scan-steps", type=int, default=8,
                    help="rolled window cap (steps per cohort dispatch)")
    ap.add_argument("--solver-backend", default="auto",
                    choices=["auto", "fused", "reference"])
    ap.add_argument("--pipeline", default="auto",
                    choices=["auto", "on", "off"],
                    help="software-pipelined rolled windows per tenant "
                         "(auto: pipeline whenever the tenant's program "
                         "declares a pipeline form; off: serial fused)")
    ap.add_argument("--xla-tuning", action="store_true",
                    help="apply repro.env.configure_platform()'s XLA "
                         "latency-hiding/async-stream flags before "
                         "backend init")
    # -- open-loop arrivals (continuous-batching scheduler) ----------------
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="Poisson arrival rate (sessions/s of virtual "
                         "time); > 0 switches to the EngineScheduler "
                         "driver with a heterogeneous size-class mix")
    ap.add_argument("--deadline-ms", type=float, default=50.0,
                    help="per-step latency target of deadline tenants")
    ap.add_argument("--deadline-frac", type=float, default=0.25,
                    help="fraction of arrivals in the deadline class")
    ap.add_argument("--max-wait-rounds", type=int, default=4,
                    help="bulk anti-starvation bound (scheduler rounds)")
    ap.add_argument("--lane-classes", action="store_true",
                    help="pad cohort batch axes to powers of two")
    ap.add_argument("--cases", default="cavity",
                    help="comma-separated flow cases sampled per arrival "
                         "(cohort keys split on case: mixed-case tenants "
                         "never co-batch)")
    ap.add_argument("--programs", default="piso",
                    help="comma-separated timestep programs (piso,simple) "
                         "sampled per arrival")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--supervise", action="store_true",
                    help="attach a SessionSupervisor to every session "
                         "(divergence detection, backoff, quarantine)")
    ap.add_argument("--chaos", default=None, metavar="KINDS",
                    help="deterministic fault injection: 'all' or a "
                         "comma list of nan,blowup,cap,slow "
                         "(implies --supervise)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="fault-schedule seed (defaults to --seed)")
    ap.add_argument("--chaos-events", type=int, default=0,
                    help="number of scheduled faults (0 = one per two "
                         "sessions)")
    ap.add_argument("--fallback-backend", default="",
                    help="solver backend quarantined sessions fall back "
                         "to (e.g. 'reference'; empty = keep backend)")
    ap.add_argument("--snapshot-dir", default="",
                    help="engine checkpoint directory (written at "
                         "--snapshot-every boundaries and at exit)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="snapshot once all sessions pass each multiple "
                         "of this step count (0 = only at exit)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the engine from --snapshot-dir and "
                         "continue to --steps total steps per session")
    args = ap.parse_args()

    if args.xla_tuning:
        # must precede backend init (importing jax above is fine — XLA
        # reads the env on first backend *use*, not on import)
        from repro.env import configure_platform

        configure_platform()
    enable_compile_cache()

    if args.sessions > 0 or args.resume:
        if (args.supervise or args.resume or args.chaos is not None
                or args.snapshot_dir):
            serve_cfd_supervised(args)
        elif args.arrival_rate > 0:
            serve_cfd_arrivals(args)
        else:
            serve_cfd(args)
        return
    if args.arch is None:
        ap.error("--arch is required (or use --sessions N for CFD mode)")

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = lm.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)
    frontend = None
    if cfg.frontend:
        frontend = jnp.asarray(
            rng.standard_normal((args.batch, cfg.frontend_len, cfg.d_model))
            * 0.02, jnp.float32)
    t0 = time.time()
    out = generate(cfg, params, prompts, args.n_new, frontend=frontend)
    dt = time.time() - t0
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({args.batch * args.n_new / dt:.1f} tok/s)")
    print(np.asarray(out)[:2])


if __name__ == "__main__":
    main()
