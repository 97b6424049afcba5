"""Batched serving engine: prefill, greedy decode loop, simple scheduler.

``serve_step`` is the unit the dry-run lowers for decode shapes: one new
token for every sequence in the batch against a KV cache of ``seq_len``.
``generate`` drives it for real batches (examples/serve_lm.py).

The second half of the module is the CFD serving analogue:
:class:`SimulationEngine` hosts many concurrent segregated-solver
simulations ("solver-as-a-service") — any registered ``(program, case)``
pair: transient PISO or steady SIMPLE on any flow case — each with its
**own**
:class:`~repro.core.controller.RepartitionController` — per-session
calibration state, so a session on a coarse mesh with heavy assembly and a
session on a fine mesh with a dominant solve adapt their alpha
independently — while all sessions share one process-wide
:class:`~repro.core.controller.PlanCache` (plans are immutable and keyed by
mesh fingerprint, so a newly opened session on an already-seen mesh starts
with warm plans).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.controller import (ControllerConfig, PlanCache,
                                   RepartitionController)
from repro.core.cost_model import CostModel, hardware_for
from repro.models import lm
from repro.models.config import ModelConfig


class ServeState(NamedTuple):
    cache: dict
    last_tokens: jax.Array  # (B, 1)
    pos: jax.Array          # scalar int32 — next write position


def serve_step(cfg: ModelConfig, params, state: ServeState):
    """One greedy decode step for the whole batch."""
    logits, cache = lm.decode_step(cfg, params, state.cache,
                                   state.last_tokens, state.pos)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    return ServeState(cache=cache, last_tokens=nxt, pos=state.pos + 1), nxt


def start(cfg: ModelConfig, params, prompts: jax.Array, max_len: int,
          frontend=None) -> tuple[ServeState, jax.Array]:
    """Prefill the prompts and return the initial serve state."""
    logits, cache = lm.prefill(cfg, params, prompts, max_len,
                               frontend=frontend)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    n_prefix = cfg.frontend_len if cfg.frontend == "vision_stub" else 0
    pos = jnp.asarray(prompts.shape[1] + n_prefix, jnp.int32)
    return ServeState(cache=cache, last_tokens=first, pos=pos), first


def generate(cfg: ModelConfig, params, prompts: jax.Array, n_new: int,
             frontend=None) -> jax.Array:
    """Greedy generation of ``n_new`` tokens.  Returns (B, n_new).

    ``n_new=0`` is a pure no-op: no prefill, no decode loop, an empty
    ``(B, 0)`` token block (the prefill argmax used to be appended
    unconditionally, returning one token nobody asked for).
    """
    if n_new < 0:
        raise ValueError(f"n_new must be >= 0, got {n_new}")
    if n_new == 0:
        return jnp.zeros((prompts.shape[0], 0), jnp.int32)
    max_len = prompts.shape[1] + n_new + (
        cfg.frontend_len if cfg.frontend == "vision_stub" else 0)
    state, first = start(cfg, params, prompts, max_len, frontend)
    step = jax.jit(functools.partial(serve_step, cfg))

    outs = [first]
    for _ in range(n_new - 1):
        state, nxt = step(params, state)
        outs.append(nxt)
    return jnp.concatenate(outs, axis=1)


# ---------------------------------------------------------------------------
# CFD simulation serving — multi-tenant PISO with per-session adaptation.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SimulationSession:
    """One tenant: a solver, its private controller, and its flow state."""

    sid: str
    solver: object                      # PisoSolver
    controller: RepartitionController
    state: object                       # PisoState
    dt: float
    mesh_fp: str = ""                   # structural mesh hash (cohort key)
    adaptive: bool = True
    steps_done: int = 0
    # serving-policy metadata (consumed by serving.scheduler): priority
    # class and, for deadline tenants, the per-session-step target
    priority: str = "bulk"
    deadline_ms: float | None = None
    # per-session-step wall latencies (seconds), appended when the engine
    # runs with track_latency=True; stats() folds them into p50/p99
    latency_samples: list = dataclasses.field(default_factory=list)
    # health state machine (serving.supervisor) — None when the engine
    # runs unsupervised (the default; legacy behavior is bit-identical)
    supervisor: object | None = None


class SimulationEngine:
    """Concurrent PISO simulations with independent adaptive repartitioning.

    Controller state (calibration EMA, hysteresis counters, switch history)
    is strictly per session; the :class:`PlanCache` — symbolic plans plus the
    compiled-update pool — is shared, which is safe because plans are
    immutable and keyed by ``(mesh fingerprint, alpha, target)``.

    Sessions advance either one at a time (:meth:`step_session`) or — the
    throughput path — in **cohorts** (:meth:`step_all`): open sessions
    whose compiled program is interchangeable (same mesh structure, alpha,
    solve mode, solver backend, viscosity, timestep program and flow
    case) are stacked along a leading
    session axis and advance through ONE batched XLA dispatch per rolled
    window instead of one per tenant, the batching cure for the
    undersubscribed-dispatch regime (one tenant per launch collapses
    device utilization exactly like the paper's undersubscribed GPU).
    """

    def __init__(self, plan_cache: PlanCache | None = None,
                 config: ControllerConfig | None = None,
                 scan_window: int = 8, lane_classes: bool = False,
                 track_latency: bool = False, clock=None,
                 supervise: bool = False, supervisor_config=None):
        # explicit None test: an empty PlanCache is falsy (it has __len__)
        self.plan_cache = PlanCache() if plan_cache is None else plan_cache
        # per-instance default: a shared ControllerConfig() *instance*
        # default argument would alias every engine constructed without an
        # explicit config to one object
        self.config = ControllerConfig() if config is None else config
        if scan_window < 1:
            raise ValueError("scan_window must be >= 1")
        # max steps per rolled lax.scan dispatch: bounds the set of compiled
        # window lengths (each distinct length is its own XLA program)
        self.scan_window = scan_window
        # lane classes: pad every *padded* (size-class) cohort's batch axis
        # to the next power of two with zero filler lanes, so mid-window
        # admissions/evictions move a cohort between a handful of compiled
        # batch shapes instead of recompiling per occupancy.  Filler lanes
        # carry n_active=0 — every mask is zero, the Krylov loops converge
        # instantly — so the marginal cost is near nil.  Plain (unpadded)
        # cohorts are exempt: without the n_active operand a filler lane
        # would assemble a real lid-driven system.
        self.lane_classes = lane_classes
        # latency accounting: when on, every stepping path blocks on its
        # result and books wall time per session-step (stats() reports
        # p50/p99 per priority class).  ``clock`` is injectable so the
        # deterministic scheduler harness can drive a virtual clock.
        self.track_latency = track_latency
        import time as _time

        self._clock = _time.perf_counter if clock is None else clock
        # supervised mode: every session gets a SessionSupervisor that
        # watches the compiled health flags per window, rolls faulty
        # sessions back to their last clean snapshot, and escalates
        # degraded → quarantined → failed (serving.supervisor).  Costs one
        # tiny host readback of the flag words per window, so it is
        # opt-in; unsupervised engines are untouched.
        self.supervise = supervise
        if supervise:
            from repro.serving.supervisor import SupervisorConfig

            self.supervisor_config = (SupervisorConfig()
                                      if supervisor_config is None
                                      else supervisor_config)
        else:
            self.supervisor_config = supervisor_config
        # failed sessions' post-mortems: sid -> final stats + event log
        self.failed: dict[str, dict] = {}
        self.sessions: dict[str, SimulationSession] = {}
        # dispatch accounting for the two stepping paths: "solo" counts
        # single-session fused launches, "cohort" one launch per batched
        # cohort window (the quantity step_all exists to shrink)
        self.counters = {"solo_dispatches": 0, "cohort_dispatches": 0,
                         "sample_steps": 0, "rolled_windows": 0,
                         "scheduling_rounds": 0}
        # per-executor-path breakdown of the rolled-window launches above:
        # which stepping executor served each dispatch (solo vs cohort,
        # serial fused vs software-pipelined).  Sample steps always run
        # the serial instrumented schedule, so they are not split here.
        self.dispatch_paths = {"solo": 0, "cohort": 0,
                               "pipelined_solo": 0, "pipelined_cohort": 0}

    def open_session(self, sid: str, mesh, *, dt: float,
                     alpha0: int | None = None, nu: float = 0.01,
                     model: CostModel | None = None,
                     adaptive: bool = True,
                     solve_mode: str = "stacked",
                     solver_backend: str = "auto",
                     pad_to_class: int | None = None,
                     priority: str = "bulk",
                     deadline_ms: float | None = None,
                     program: str = "piso",
                     case: str = "cavity",
                     pipeline: str = "auto",
                     precision: str = "f64") -> SimulationSession:
        """Admit a simulation; its controller starts from the cost model's
        static pick (``alpha0=None``) exactly like the non-adaptive launcher,
        then departs from it as measurements arrive.  ``solve_mode``
        ("stacked" | "full_mesh") picks the SPMD solve layout per tenant —
        a full-mesh session needs ``mesh.n_parts`` visible devices and keys
        its cached plans/steppers separately from stacked sessions.
        ``solver_backend`` ("auto" | "fused" | "reference") picks the
        per-tenant Krylov iteration backend (:mod:`repro.solvers.ops`);
        a fused session models the fused bytes/iter term and keys its
        cached artifacts separately too.

        ``pad_to_class`` zero-pads the mesh's part axis to that **size
        class** (:class:`~repro.fvm.mesh.PaddedCavityMesh`) so tenants
        whose meshes share a per-part structure but differ in slab count
        land in ONE cohort — the scheduler's cure for heterogeneous-mix
        fragmentation.  ``priority`` ("bulk" | "deadline") and
        ``deadline_ms`` feed the scheduling policy
        (:mod:`repro.serving.scheduler`); they do not change the numerics.

        ``program`` ("piso" | "simple" — ``repro.fvm.piso.SOLVERS``) and
        ``case`` (a ``repro.fvm.cases`` registry name) pick the tenant's
        timestep program and flow-case BC set; both are cohort-key
        components, so heterogeneous tenants never co-batch across a
        program or case boundary.

        ``pipeline`` ("auto" | "on" | "off") selects the software-
        pipelined stepper for this tenant's rolled windows
        (:class:`~repro.fvm.step_program.PipelinedExecutor`); "auto"
        resolves per program (PISO pipelines, steady programs fall back
        to serial).  The resolved boolean is a cohort-key component and
        is handed to the session's controller so alpha selection scores
        the overlap objective instead of the serial sum.

        ``precision`` ("f64" | "f32_ir" | "bf16_ir",
        :mod:`repro.solvers.precision`) picks the tenant's mixed-precision
        Krylov policy.  It is a cohort-key component (mixed-precision
        tenants never co-batch with f64 ones), re-prices the controller's
        bytes/iter term, and is the supervisor's first fallback ladder on
        faults (``bf16_ir -> f32_ir -> f64`` before any backend rebind).
        """
        from repro.core.repartition import mesh_fingerprint
        from repro.fvm.mesh import PaddedCavityMesh
        from repro.fvm.piso import make_solver
        from repro.fvm.step_program import get_program

        if sid in self.sessions:
            raise ValueError(f"session {sid!r} already open")
        if priority not in ("bulk", "deadline"):
            raise ValueError(f"unknown priority {priority!r}")
        if pad_to_class is not None:
            mesh = PaddedCavityMesh.pad(mesh, pad_to_class)
        # cost honesty for padded meshes: ghost slabs carry no dofs
        n_dofs = getattr(mesh, "n_cells_active", mesh.n_cells_global)
        model = model or CostModel(hardware_for(jax.devices()[0]),
                                   n_dofs=n_dofs)
        # fixed_fine feasibility already restricts alphas to divisors of
        # n_cpu = mesh.n_parts, i.e. to plans realizable on the mesh
        # resolve the pipeline knob against the program spec up front so
        # the controller's *initial* alpha pick already scores the overlap
        # objective (the solver re-resolves and validates the same knob)
        if pipeline not in ("auto", "on", "off"):
            raise ValueError(f"unknown pipeline mode {pipeline!r} "
                             "(choose auto|on|off)")
        pipelined = (pipeline == "on"
                     or (pipeline == "auto"
                         and get_program(program).pipelined))
        controller = RepartitionController(
            model, n_cpu=mesh.n_parts, n_gpu=1, alpha0=alpha0,
            config=self.config, cache=self.plan_cache, fixed_fine=True,
            solve_mode=solve_mode, solver_backend=solver_backend,
            pipelined=pipelined, precision=precision)
        solver = make_solver(program, mesh, alpha=controller.alpha, nu=nu,
                             case=case, plan_cache=self.plan_cache,
                             solve_mode=solve_mode,
                             solver_backend=solver_backend,
                             pipeline=pipeline, precision=precision)
        sess = SimulationSession(sid=sid, solver=solver,
                                 controller=controller,
                                 state=solver.initial_state(), dt=dt,
                                 mesh_fp=mesh_fingerprint(mesh),
                                 adaptive=adaptive, priority=priority,
                                 deadline_ms=deadline_ms)
        if self.supervise:
            from repro.serving.supervisor import SessionSupervisor

            sess.supervisor = SessionSupervisor(self.supervisor_config)
            # the initial condition is by definition a clean snapshot
            sess.supervisor.checkpoint(sess.state, 0)
        self.sessions[sid] = sess
        return sess

    def step_session(self, sid: str, n_steps: int = 1):
        """Advance one tenant; other sessions' controllers are untouched.

        The engine executor of the StepProgram: non-sample steps advance
        through the fused **scan-rolled** stepper (`PisoSolver.run_steps`
        — the whole stretch to the next sample point is one XLA
        dispatch), and only every ``ControllerConfig.sample_every``-th
        timestep runs the per-phase **instrumented** stepper whose
        ``PhaseBreakdown`` feeds the controller.  Adaptation therefore no
        longer serializes every timestep behind ``block_until_ready``
        phase timers; the controller sees exactly the sampled
        subsequence (its warmup/patience/dwell count sampled steps).
        The sampling grid is anchored to ``steps_done``
        (:func:`repro.fvm.step_program.roll_schedule`), so the cadence is
        stable across repeated ``step_session`` calls; rolled windows are
        capped at ``scan_window`` steps so a long request cannot compile
        one ``lax.scan`` program per distinct length.  Returns the last
        step's ``StepStats``.
        """
        from repro.fvm.step_program import roll_schedule

        sess = self.sessions[sid]
        if sess.supervisor is not None:
            # supervised sessions may roll back mid-request, which
            # invalidates a pre-computed schedule — drive them through the
            # target-based loop instead
            return self.step_all(n_steps, sids=[sid]).get(sid)
        every = self._every(sess)
        stats = None
        for is_sample, chunk in roll_schedule(sess.steps_done, n_steps,
                                              every, cap=self.scan_window):
            stats = self._advance_one(sess, is_sample, chunk)
        return stats

    def _every(self, sess: SimulationSession) -> int | None:
        """The session's sampling cadence: ``sample_every`` for adaptive
        sessions, None otherwise — and None while a supervised session is
        unhealthy (a degraded tenant's timings would feed the controller
        retry noise, and its rolled-back step counter would thrash the
        cohort sampling phase)."""
        healthy = sess.supervisor is None or sess.supervisor.healthy
        return (self.config.sample_every
                if (sess.adaptive and healthy) else None)

    # ---- cohort-batched stepping ----------------------------------------
    def _advance_one(self, sess: SimulationSession, is_sample: bool,
                     chunk: int):
        """Advance one session through one schedule stretch (solo path)."""
        t0 = self._clock() if self.track_latency else 0.0
        sup = sess.supervisor
        dt = sess.dt if sup is None else sess.dt * sup.dt_scale
        sample = None
        if is_sample:
            sess.state, stats, sample = sess.solver.timed_step(
                sess.state, dt)
            self.counters["sample_steps"] += 1
            window = stats
        else:
            sess.state, window = sess.solver.run_steps(
                sess.state, dt, chunk)
            stats = jax.tree.map(lambda a: a[-1], window)
            self.counters["solo_dispatches"] += 1
            self.counters["rolled_windows"] += 1
            self.dispatch_paths[
                "pipelined_solo"
                if getattr(sess.solver, "pipelined", False)
                else "solo"] += 1
        if self.track_latency:
            jax.block_until_ready(sess.state)
            per_step = (self._clock() - t0) / chunk
            sess.latency_samples.extend([per_step] * chunk)
        sess.steps_done += chunk
        verdict = self._supervise(sess, window) if sup is not None else None
        if sample is not None and verdict is None:
            alpha = sess.controller.step(sample)
            if alpha != sess.solver.alpha:
                sess.solver.rebind_alpha(alpha)
        return stats

    def _cohort_key(self, sess: SimulationSession) -> tuple:
        """Program-interchangeability key: sessions mapping to equal keys
        step through ONE batched executor.

        ``(mesh fingerprint, alpha, solve_mode, solver_backend)`` is the
        compiled-program identity (plus ``nu``/dtype, which the program
        closes over); adaptive sessions additionally carry their sampling
        phase (``steps_done mod sample_every``) so every cohort member
        agrees on where the next instrumented sample falls — sessions out
        of phase simply land in sibling cohorts until they re-align.

        A **size-class** (padded) session keys on its *class* shape: a
        :class:`~repro.fvm.mesh.PaddedCavityMesh` fingerprints identically
        to a plain mesh of the padded shape, so every tenant padded to one
        class shares a fingerprint whatever its real slab count — but the
        padded program takes the extra traced ``n_active`` operand, so
        ``padded`` is its own key component (a padded and a plain session
        of equal shape are NOT program-interchangeable).

        ``(program_name, case)`` are key components too: a PISO and a
        SIMPLE tenant compile different phase lists, and two cases bind
        different BC masks/boundary sources into the assembly closures —
        mixed-program or mixed-case tenants are never co-batched.  The
        resolved ``pipelined`` flag likewise: a software-pipelined and a
        serial tenant compile different rolled bodies (ring-carried
        schedule vs phase-ordered scan), so they dispatch separately even
        when everything else matches.
        """
        s = sess.solver
        phase = (sess.steps_done % self.config.sample_every
                 if sess.adaptive else -1)
        # supervision token: an unhealthy session keys on its own sid, so
        # it steps solo (degraded retries replay private windows; a
        # quarantined tenant must not drag its rollbacks, scaled dt or
        # fallback backend into a shared dispatch) while healthy
        # cohort-mates keep their 1-dispatch window.  Recovery clears the
        # token and the session re-joins its cohort on the next round.
        quarantine = (None if sess.supervisor is None
                      or sess.supervisor.healthy else sess.sid)
        # Krylov tolerances/caps are compiled into the program (and into
        # the health flags): a session whose solve config was retuned at
        # runtime is no longer numerically interchangeable with its old
        # cohort — it must step through its own rebuilt executor, not
        # silently ride the lead session's
        tols = (s.mom_tol, s.p_tol, getattr(s, "mom_maxiter", 500),
                getattr(s, "p_maxiter", 2000))
        # precision is a key component for the same compiled-identity
        # reason: a mixed-precision tenant's program runs the outer
        # refinement loop (different jaxpr, different storage dtypes) —
        # it must never co-batch with an f64 tenant's dispatch
        return (sess.mesh_fp, s.alpha, s.solve_mode, s.solver_backend,
                s.nu, str(s.dtype), sess.adaptive, phase, tols,
                getattr(s, "padded", False),
                getattr(s, "program_name", "piso"),
                getattr(s, "case", "cavity"),
                getattr(s, "pipelined", False),
                getattr(s, "precision", "f64"), quarantine)

    def step_all(self, n_steps: int = 1, sids=None) -> dict:
        """Advance every open session (or ``sids``) by ``n_steps`` through
        cohort-batched dispatches; returns the last ``StepStats`` per sid.

        Scheduling runs in rounds: sessions are grouped by
        :meth:`_cohort_key`, each cohort's ``PisoState`` leaves are stacked
        along a leading session axis (``repro.fvm.piso.stack_states``) and
        the cohort advances through one schedule stretch of the shared
        ``roll_schedule`` cadence via the leader's
        :meth:`~repro.fvm.piso.PisoSolver.batched_executor` — a rolled
        window of S tenants is ONE XLA dispatch instead of S.  Per-session
        ``dt`` rides along as a traced vector, so mixed-timestep tenants
        share one compiled program.

        Controllers stay independent: a sampled stretch runs the batched
        instrumented walk, unstacks its per-session ``PhaseBreakdown``
        rows into each tenant's controller, and a session whose controller
        switches alpha rebinds immediately — the changed cohort key
        migrates it to its new cohort on the next scheduling round.
        Singleton cohorts and full-mesh sessions (whose ``shard_map``
        solve pins a device layout that cannot be vmapped over sessions)
        take the solo path inside the same schedule.
        """
        if n_steps < 0:
            raise ValueError(f"n_steps must be >= 0, got {n_steps}")
        sids = list(self.sessions if sids is None else sids)
        missing = [sid for sid in sids if sid not in self.sessions]
        if missing:
            raise KeyError(f"unknown session(s) {missing}")
        # target-based accounting (absolute step goals, not remaining
        # decrements): a supervised rollback moves steps_done backwards
        # and the session simply stays live until it re-earns its target;
        # a FAILED session leaves self.sessions and drops out of the loop
        # (its retry budget bounds the extra rounds, so this terminates)
        target = {sid: self.sessions[sid].steps_done + n_steps
                  for sid in sids}
        last: dict[str, object] = {}
        while True:
            live = [sid for sid in target
                    if sid in self.sessions
                    and self.sessions[sid].steps_done < target[sid]]
            if not live:
                break
            self.counters["scheduling_rounds"] += 1
            cohorts: dict[tuple, list[str]] = {}
            for sid in live:
                key = self._cohort_key(self.sessions[sid])
                cohorts.setdefault(key, []).append(sid)
            for group in cohorts.values():
                # a supervised failure earlier in this round may have
                # closed a member of a later group
                group = [sid for sid in group if sid in self.sessions]
                if not group:
                    continue
                rem = min(target[sid] - self.sessions[sid].steps_done
                          for sid in group)
                self.advance_group(group, rem, last)
        return last

    def advance_group(self, group, n_steps: int, last=None) -> int:
        """Advance one cohort ``group`` (sids sharing a cohort key) through
        ONE stretch of the shared cadence; returns the stretch length.

        The scheduling quantum :class:`~repro.serving.scheduler`
        dispatches: a scheduler round picks which cohorts advance, this
        method advances one of them by a single rolled-window (or sampled)
        stretch, so admission/eviction decisions interleave at stretch
        boundaries without touching a compiled program.  ``last``, when
        given, collects each member's latest ``StepStats`` under its sid.
        """
        from repro.fvm.step_program import roll_schedule

        if n_steps < 1:
            raise ValueError(f"n_steps must be >= 1, got {n_steps}")
        last = {} if last is None else last
        lead = self.sessions[group[0]]
        # cohort contract: every member must be program-interchangeable
        # with the lead.  An external scheduler handing us a mixed group
        # (a mis-migrated tenant, a program/case mismatch) would otherwise
        # stack states into a compiled program with the wrong BC masks —
        # silently wrong physics, so reject loudly instead.
        lead_key = self._cohort_key(lead)
        bad = [sid for sid in group[1:]
               if self._cohort_key(self.sessions[sid]) != lead_key]
        if bad:
            raise ValueError(
                f"advance_group: session(s) {bad} are not cohort-"
                f"compatible with lead {group[0]!r} (program/case/mesh/"
                "alpha mismatch) — migration across cohort keys must go "
                "through a new scheduling round, not a mixed dispatch")
        every = self._every(lead)
        # one stretch of the shared cadence — the cohort key pins the
        # sampling phase, so the stretch is valid for every member
        # regardless of absolute steps_done
        is_sample, chunk = next(roll_schedule(
            lead.steps_done, n_steps, every, cap=self.scan_window))
        if len(group) == 1 or lead.solver.solve_mode == "full_mesh":
            for sid in group:
                last[sid] = self._advance_one(self.sessions[sid],
                                              is_sample, chunk)
        else:
            self._advance_cohort(group, is_sample, chunk, last)
        return chunk

    def _advance_cohort(self, group, is_sample: bool, chunk: int,
                        last) -> None:
        """Advance one multi-session cohort through one schedule stretch.

        A padded (size-class) cohort threads the per-session ``n_active``
        vector through the batched executor — each lane's activity masks
        are computed from its own real slab count inside the compiled
        program.  With ``lane_classes`` on, the batch axis is additionally
        padded to the next power of two with zero **filler lanes**
        (``n_active=0``, ``dt`` copied from the lead so the ``V/dt``
        diagonal stays finite): a cohort whose occupancy drifts between
        scheduler rounds reuses one of log2(S) compiled batch shapes
        instead of recompiling per occupancy.
        """
        from repro.fvm.piso import stack_states, unstack_states

        sessions = [self.sessions[sid] for sid in group]
        lead = sessions[0]
        padded = getattr(lead.solver, "padded", False)
        n = len(group)
        lanes = n
        if self.lane_classes and padded:
            from repro.serving.scheduler import size_class

            lanes = size_class(n)
        exe = lead.solver.batched_executor(lanes)
        states = stack_states([s.state for s in sessions], pad_to=lanes)
        dts = jnp.asarray([s.dt for s in sessions]
                          + [lead.dt] * (lanes - n), lead.solver.dtype)
        # per-lane extra operands, driven by the program's extra_keys
        # (n_active for padded programs, SIMPLE's relaxation factors);
        # filler lanes carry the lead's filler values (n_active=0)
        per_lane = ([s.solver._extras() for s in sessions]
                    + [lead.solver._filler_extras()] * (lanes - n))
        extras = tuple(jnp.stack(col) for col in zip(*per_lane))
        t0 = self._clock() if self.track_latency else 0.0
        if is_sample:
            states, stats, rows = exe.timed_step(states, dts, *extras)
            self.counters["sample_steps"] += 1
            per_stats = [jax.tree.map(lambda a, i=i: a[i], stats)
                         for i in range(n)]
        else:
            states, window = exe.run_steps(states, dts, chunk, *extras)
            self.counters["cohort_dispatches"] += 1
            self.counters["rolled_windows"] += 1
            self.dispatch_paths[
                "pipelined_cohort"
                if getattr(lead.solver, "pipelined", False)
                else "cohort"] += 1
            rows = None
            per_stats = [jax.tree.map(lambda a, i=i: a[-1, i], window)
                         for i in range(n)]
        if self.track_latency:
            jax.block_until_ready(states)
            per_step = (self._clock() - t0) / chunk
        for i, (sess, state) in enumerate(zip(sessions,
                                              unstack_states(states, n))):
            sess.state = state
            sess.steps_done += chunk
            last[sess.sid] = per_stats[i]
            if self.track_latency:
                sess.latency_samples.extend([per_step] * chunk)
            verdict = None
            if sess.supervisor is not None:
                # this lane's flag column over the whole window: vmap
                # lanes are independent, so a poisoned neighbour never
                # perturbs this verdict (or this lane's numerics)
                lane_window = (per_stats[i] if rows is not None
                               else jax.tree.map(lambda a, i=i: a[:, i],
                                                 window))
                verdict = self._supervise(sess, lane_window)
            if rows is not None and verdict is None:
                alpha = sess.controller.step(rows[i])
                if alpha != sess.solver.alpha:
                    # rebind now; the new cohort key migrates the session
                    # on the next scheduling round
                    sess.solver.rebind_alpha(alpha)

    # ---- supervision -----------------------------------------------------
    def _supervise(self, sess: SimulationSession, window_stats):
        """Apply one window's health verdict to a supervised session.

        Clean window: checkpoint the state and let the supervisor count
        toward recovery (restoring the original backend on
        QUARANTINED → DEGRADED and the original precision policy on
        DEGRADED → HEALTHY).  Faulty window: roll the session back to
        its last clean snapshot and escalate.  Mixed-precision tenants
        first climb the precision ladder (``bf16_ir → f32_ir → f64``,
        one rung per fault) — a low-precision divergence is most often
        cured by more mantissa, and a precision rebind is far cheaper
        than a backend swap; only once the ladder is exhausted does
        "quarantine" rebind the configured fallback backend.  "fail"
        closes the session and parks its post-mortem in :attr:`failed`.
        Returns the supervisor directive (None for a clean window).
        """
        import dataclasses as _dc

        from repro.serving.supervisor import FAILED, window_verdict
        from repro.solvers.precision import PRECISION_FALLBACK

        sup = sess.supervisor
        if sup is None or sup.state == FAILED:
            return None
        kind = window_verdict(window_stats)
        if kind is None:
            act = sup.on_clean_window(sess.steps_done)
            if act == "recover" and sup.orig_backend is not None:
                self._rebind_backend(sess, sup.orig_backend)
                sup.orig_backend = None
            if act == "restore" and sup.orig_precision is not None:
                self._rebind_precision(sess, sup.orig_precision)
                sup.orig_precision = None
            sup.checkpoint(sess.state, sess.steps_done)
            return None
        act = sup.on_fault(kind, sess.steps_done)
        if act == "fail":
            final = self.close_session(sess.sid)
            self.failed[sess.sid] = {
                "steps_done": sess.steps_done,
                "controller": final,
                "events": [_dc.asdict(e) for e in sup.events],
            }
            return act
        # roll back to the pre-fault snapshot; the halved dt (and any
        # precision/backend rebind below) applies to the replay
        sess.state, sess.steps_done = sup.rollback()
        nxt = PRECISION_FALLBACK.get(getattr(sess.solver, "precision",
                                             "f64"))
        if nxt is not None:
            # precision ladder first: one rung toward f64 per fault
            if sup.orig_precision is None:
                sup.orig_precision = sess.solver.precision
            self._rebind_precision(sess, nxt)
        elif act == "quarantine" and sup.config.fallback_backend:
            fb = sup.config.fallback_backend
            if sess.solver.solver_backend != fb:
                sup.orig_backend = sess.solver.solver_backend
                self._rebind_backend(sess, fb)
        return act

    def _rebind_backend(self, sess: SimulationSession, backend: str):
        """Swap the session's Krylov per-iteration backend in place; the
        solver memoizes executors per (program, alpha, mode, backend), so
        a backend the session used before rebinds without a retrace."""
        sess.solver.solver_backend = backend
        sess.controller.solver_backend = backend
        sess.solver.rebind_alpha(sess.solver.alpha)

    def _rebind_precision(self, sess: SimulationSession, precision: str):
        """Swap the session's precision policy in place.  Same memoized
        executor mechanics as :meth:`_rebind_backend` — the policy is a
        component of the solver's executor key — plus the cohort key:
        the session stops co-batching with its old-policy cohort-mates
        on the next dispatch."""
        if getattr(sess.solver, "precision", "f64") == precision:
            return
        sess.solver.precision = precision
        sess.controller.precision = precision
        base = sess.controller.base_model
        if getattr(base, "precision", "f64") != precision:
            sess.controller.base_model = base.with_precision(precision)
        sess.solver.rebind_alpha(sess.solver.alpha)

    # ---- exact checkpoint/restore ---------------------------------------
    def snapshot(self, path, scheduler=None) -> None:
        """Serialize the whole engine to ``path`` (a directory): every
        session's PisoState leaves (plus its supervisor's ``last_good``
        snapshot), controller calibration + decision state, supervisor
        state machine, dispatch counters and — when a scheduler is handed
        in — its bookkeeping.  Written atomically (tmp + rename) in the
        ``training/checkpoint.py`` idiom: one ``arrays.npz`` of leaves and
        one ``manifest.json`` of everything else, so
        :meth:`restore` resumes **exactly** — same states, same controller
        decisions, same supervision posture.
        """
        import json
        import os
        import shutil

        import numpy as np

        from repro.fvm.piso import PisoState

        arrays: dict[str, np.ndarray] = {}
        sessions = []
        for sid, sess in self.sessions.items():
            for field, leaf in zip(PisoState._fields, sess.state):
                arrays[f"{sid}|state|{field}"] = np.asarray(leaf)
            sup = sess.supervisor
            if sup is not None and sup.last_good is not None:
                for field, leaf in zip(PisoState._fields, sup.last_good[0]):
                    arrays[f"{sid}|good|{field}"] = np.asarray(leaf)
            c = sess.controller
            mesh = sess.solver.mesh
            sessions.append({
                "sid": sid,
                "mesh": {"nx": mesh.nx, "ny": mesh.ny, "nz": mesh.nz,
                         "n_parts": mesh.n_parts, "h": mesh.h,
                         "n_parts_real": getattr(mesh, "n_parts_real",
                                                 None)},
                "dt": sess.dt, "adaptive": sess.adaptive,
                "steps_done": sess.steps_done,
                "priority": sess.priority, "deadline_ms": sess.deadline_ms,
                "program": getattr(sess.solver, "program_name", "piso"),
                "case": str(getattr(sess.solver, "case", "cavity")),
                "nu": sess.solver.nu,
                "alpha": sess.solver.alpha,
                "solve_mode": sess.solver.solve_mode,
                "solver_backend": sess.solver.solver_backend,
                "pipeline": getattr(sess.solver, "pipeline", "auto"),
                "precision": getattr(sess.solver, "precision", "f64"),
                "latency_samples": list(sess.latency_samples),
                "controller": {
                    "alpha": c.alpha,
                    "step_count": c.step_count,
                    "last_switch_step": c.last_switch_step,
                    "calibration": {
                        "log_scales": list(c.calibration._log_scales),
                        "n_obs": c.calibration.n_obs},
                    "switches": [dataclasses.asdict(s) for s in c.switches],
                    "history": [dataclasses.asdict(h) for h in c.history],
                    "challenger": c._challenger,
                    "challenger_wins": c._challenger_wins,
                },
                "supervisor": None if sup is None else sup.to_dict(),
            })
        manifest = {
            "format": 1,
            "engine": {
                "scan_window": self.scan_window,
                "lane_classes": self.lane_classes,
                "track_latency": self.track_latency,
                "supervise": self.supervise,
                "supervisor_config": (
                    None if self.supervisor_config is None
                    else dataclasses.asdict(self.supervisor_config)),
                "config": dataclasses.asdict(self.config),
                "counters": dict(self.counters),
                "dispatch_paths": dict(self.dispatch_paths),
            },
            "failed": self.failed,
            "scheduler": (None if scheduler is None
                          else scheduler.bookkeeping()),
            "sessions": sessions,
        }
        path = os.fspath(path)
        tmp = path.rstrip("/") + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=1, default=float)
        if os.path.exists(path):
            shutil.rmtree(path)
        os.rename(tmp, path)

    @classmethod
    def restore(cls, path, plan_cache: PlanCache | None = None,
                clock=None) -> "SimulationEngine":
        """Rebuild an engine from :meth:`snapshot` output.  Sessions are
        re-opened in manifest order (so cohort stacking order — and hence
        batched reduction order — matches the snapshotting engine), then
        every leaf, counter and decision variable is overwritten with the
        serialized value: the resumed engine's next window is bit-identical
        to what the snapshotted engine would have computed."""
        import json
        import os

        import numpy as np

        from repro.core.controller import SwitchEvent
        from repro.core.cost_model import PhaseBreakdown
        from repro.fvm.mesh import CavityMesh, PaddedCavityMesh
        from repro.fvm.piso import PisoState
        from repro.serving.supervisor import (SessionSupervisor,
                                              SupervisorConfig)

        with open(os.path.join(os.fspath(path), "manifest.json")) as f:
            manifest = json.load(f)
        arrs = np.load(os.path.join(os.fspath(path), "arrays.npz"))
        e = manifest["engine"]
        cfg = dict(e["config"])
        cfg["alphas"] = tuple(cfg["alphas"])
        sup_cfg = (None if e["supervisor_config"] is None
                   else SupervisorConfig(**e["supervisor_config"]))
        eng = cls(plan_cache=plan_cache, config=ControllerConfig(**cfg),
                  scan_window=int(e["scan_window"]),
                  lane_classes=e["lane_classes"],
                  track_latency=e["track_latency"], clock=clock,
                  supervise=e["supervise"], supervisor_config=sup_cfg)
        eng.counters.update({k: int(v) for k, v in e["counters"].items()})
        # manifests written before the pipelined path carry no breakdown
        eng.dispatch_paths.update(
            {k: int(v) for k, v in e.get("dispatch_paths", {}).items()})
        eng.failed = dict(manifest["failed"])
        for m in manifest["sessions"]:
            md = m["mesh"]
            if md["n_parts_real"] is not None:
                mesh = PaddedCavityMesh(
                    nx=int(md["nx"]), ny=int(md["ny"]), nz=int(md["nz"]),
                    n_parts=int(md["n_parts"]), h=float(md["h"]),
                    n_parts_real=int(md["n_parts_real"]))
            else:
                mesh = CavityMesh(nx=int(md["nx"]), ny=int(md["ny"]),
                                  nz=int(md["nz"]),
                                  n_parts=int(md["n_parts"]),
                                  h=float(md["h"]))
            sid = m["sid"]
            sess = eng.open_session(
                sid, mesh, dt=float(m["dt"]), alpha0=int(m["alpha"]),
                nu=float(m["nu"]), adaptive=m["adaptive"],
                solve_mode=m["solve_mode"],
                solver_backend=m["solver_backend"],
                priority=m["priority"], deadline_ms=m["deadline_ms"],
                program=m["program"], case=m["case"],
                pipeline=m.get("pipeline", "auto"),
                precision=m.get("precision", "f64"))
            sess.state = PisoState(*[jnp.asarray(arrs[f"{sid}|state|{f}"])
                                     for f in PisoState._fields])
            sess.steps_done = int(m["steps_done"])
            sess.latency_samples = list(m["latency_samples"])
            c, cd = sess.controller, m["controller"]
            c.alpha = int(cd["alpha"])
            c.step_count = int(cd["step_count"])
            c.last_switch_step = int(cd["last_switch_step"])
            c.calibration._log_scales = [
                float(s) for s in cd["calibration"]["log_scales"]]
            c.calibration.n_obs = int(cd["calibration"]["n_obs"])
            c.switches = [SwitchEvent(**s) for s in cd["switches"]]
            c.history = [PhaseBreakdown(**h) for h in cd["history"]]
            c._challenger = cd["challenger"]
            c._challenger_wins = int(cd["challenger_wins"])
            if m["supervisor"] is not None:
                sup = SessionSupervisor.from_dict(m["supervisor"])
                if m["supervisor"]["last_good_step"] is not None:
                    good = PisoState(*[jnp.asarray(arrs[f"{sid}|good|{f}"])
                                       for f in PisoState._fields])
                    sup.last_good = (good,
                                     int(m["supervisor"]["last_good_step"]))
                sess.supervisor = sup
        return eng

    def close_session(self, sid: str) -> dict:
        """Evict the tenant; returns its final controller stats."""
        sess = self.sessions.pop(sid)
        return sess.controller.stats()

    def cohorts(self) -> dict:
        """The current cohort map: cohort key -> open session ids (what
        the next ``step_all`` scheduling round would batch together)."""
        out: dict[tuple, list[str]] = {}
        for sid, sess in self.sessions.items():
            out.setdefault(self._cohort_key(sess), []).append(sid)
        return out

    def reset_stats(self) -> None:
        """Zero the dispatch counters, latency samples, and plan-cache
        hit/miss meters (cached plans themselves are kept — resetting is
        about *accounting*, so a multi-config benchmark run can report
        per-config counts instead of a running total)."""
        for k in self.counters:
            self.counters[k] = 0
        for k in self.dispatch_paths:
            self.dispatch_paths[k] = 0
        for sess in self.sessions.values():
            sess.latency_samples.clear()
        reset = getattr(self.plan_cache, "reset_stats", None)
        if reset is not None:
            reset()

    def latency_stats(self) -> dict:
        """p50/p99 session-step latency, per session and pooled per
        priority class (nearest-rank percentiles; empty when the engine
        runs without ``track_latency``)."""
        from repro.serving.scheduler import percentile

        per_session, pooled = {}, {}
        for sid, s in self.sessions.items():
            if s.latency_samples:
                per_session[sid] = {
                    "n": len(s.latency_samples),
                    "p50": percentile(s.latency_samples, 50),
                    "p99": percentile(s.latency_samples, 99),
                }
            pooled.setdefault(s.priority, []).extend(s.latency_samples)
        classes = {
            prio: {"n": len(xs), "p50": percentile(xs, 50),
                   "p99": percentile(xs, 99)}
            for prio, xs in pooled.items() if xs
        }
        return {"per_session": per_session, "classes": classes}

    def stats(self) -> dict:
        return {
            "sessions": {
                sid: {"steps": s.steps_done, "alpha": s.controller.alpha,
                      "solve_mode": s.controller.solve_mode,
                      "solver_backend": s.controller.solver_backend,
                      "switches": len(s.controller.switches),
                      "priority": s.priority,
                      "program": getattr(s.solver, "program_name", "piso"),
                      "case": getattr(s.solver, "case", "cavity"),
                      "pipelined": getattr(s.solver, "pipelined", False),
                      "precision": getattr(s.solver, "precision", "f64"),
                      "health": (None if s.supervisor is None
                                 else s.supervisor.state)}
                for sid, s in self.sessions.items()
            },
            "cohorts": [len(g) for g in self.cohorts().values()],
            "counters": dict(self.counters),
            "dispatch_paths": dict(self.dispatch_paths),
            "failed": sorted(self.failed),
            "plan_cache": self.plan_cache.stats(),
            "latency": self.latency_stats(),
        }
