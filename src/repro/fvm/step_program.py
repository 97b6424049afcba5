"""StepProgram — the PISO timestep as one declarative phase graph.

The paper's whole method rests on a per-phase decomposition of one outer
iteration — assembly, coefficient update, halo exchange, solve (fig. 5/7).
The seed encoded that decomposition twice by hand: once fused inside
``PisoSolver._step_impl`` and once re-spelled phase-by-phase for the
adaptive controller's timers (``_timed_fns``) — ~150 duplicated lines of
the same dataflow that had already begun to drift.  This module makes the
decomposition *data*: a :class:`StepProgram` is an ordered tuple of named
:class:`Phase` entries — pure functions with declared env inputs/outputs
and a cost-model phase tag (:class:`~repro.core.cost_model.PhaseBreakdown`
field) — built once per ``(alpha, solve_mode, solver_backend)`` binding by
:func:`build_piso_program`, and compiled three ways from the single
definition:

* :class:`FusedExecutor` — the whole program jitted into one XLA
  executable with ``dt`` **traced** (changing the timestep size does not
  recompile) and the ``PisoState`` buffers **donated** (the input state is
  invalidated; keep the returned one).  ``run_steps(state, dt, n)`` rolls
  ``n`` timesteps into a single ``lax.scan`` dispatch and returns
  per-step stacked ``StepStats`` — a whole simulation window is one
  host→XLA launch.
* :class:`InstrumentedExecutor` — walks the same phase list with
  per-phase ``block_until_ready`` wall timers and emits a
  :class:`~repro.core.cost_model.PhaseBreakdown`.  The halo share of a
  solve phase is apportioned through the phase's declared ``probe`` hook
  (one probed exchange × the solve's iteration count — the exchange
  cannot be timed from inside the jitted Krylov loop).
* the engine executor — ``serving.engine.SimulationEngine.step_session``
  advances via the rolled fused stepper and samples the instrumented one
  only every ``ControllerConfig.sample_every`` steps, so adaptation no
  longer serializes every timestep.
* :class:`BatchedExecutor` — ``jax.vmap`` of the same program over a
  leading **session axis**: a cohort of S same-shape tenants (stacked
  ``PisoState`` leaves, a per-session ``dt`` vector) advances through one
  scan-rolled window as ONE XLA dispatch instead of S.  Donation is
  preserved (the stacked state aliases in place) and the batched
  instrumented walk emits one apportioned ``PhaseBreakdown`` row per
  session, so per-session controllers stay independent
  (``SimulationEngine.step_all`` is the consumer).
* :class:`PipelinedExecutor` — the program's **software-pipelined**
  alternative schedule (:class:`PipelineForm`): the declared phase
  inputs/outputs are compiled into a dependence DAG, independent phases
  are hoisted next to the blocking Krylov solves (the legal overlap
  frontier, computed automatically), and ring-carried values cross the
  ``lax.scan`` step boundary so step t+1's assembly consumes work issued
  during step t.  Dispatch count, dt tracing, state donation and the
  stacked ``StepStats`` semantics all match :class:`FusedExecutor`;
  :class:`BatchedPipelinedExecutor` is its cohort (vmapped) variant.

Every future phase change (overlap, mixed precision, extra correctors) is
a one-place edit to the phase list; all executors pick it up.  Each
executor that traces the whole program runs every phase under
``jax.named_scope(<phase name>)`` (:func:`_run_phase`), so the compiled
window's HLO metadata names the phase of each instruction a profiler
trace times.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.cost_model import PhaseBreakdown

__all__ = [
    "Phase", "StepProgram", "FusedExecutor", "InstrumentedExecutor",
    "BatchedExecutor", "PipelinedExecutor", "BatchedPipelinedExecutor",
    "PipelineForm", "ProgramExecutors", "build_piso_program",
    "PHASE_TAGS", "ProgramSpec", "PROGRAMS", "register_program",
    "get_program", "program_names", "PhaseToolkit",
]

# the cost-model buckets a phase may bill to (PhaseBreakdown TIME fields —
# the provenance flag ``overlapped`` is not a billable bucket)
PHASE_TAGS = PhaseBreakdown.TIME_FIELDS


@dataclasses.dataclass(frozen=True)
class Phase:
    """One named, pure step of the program.

    ``fn`` consumes ``inputs`` (env keys, positionally) and returns one
    value per name in ``outputs`` (a bare value when there is exactly
    one).  ``tag`` is the :class:`PhaseBreakdown` bucket the instrumented
    executor bills this phase to — the attribution follows the paper's two
    partitions, so e.g. the momentum predictor's phases all bill to
    ``assembly`` even though one of them is a solve.

    ``corrector`` marks per-corrector phase instances (they share ``fn``
    and therefore a single per-phase jit trace).  ``instrumented_fn``, when
    set, replaces the jitted ``fn`` in the instrumented executor only —
    the hook the plan cache uses to route value updates through its shared
    compiled-update pool.  ``probe``/``probe_inputs``/``probe_iters``
    declare the halo-apportioning hook: the instrumented executor times
    one ``probe`` dispatch, reads the iteration count from the
    ``probe_iters`` output, and bills ``min(iters * t_probe, t_phase / 2)``
    to ``halo`` with the remainder on ``tag``.

    ``blocking`` marks a latency-bound phase (a Krylov ``while_loop``
    solve) for the pipelined scheduler: the scheduler issues every
    dataflow-independent phase *before* a blocking one at the same
    dependence level, so the compiler sees the overlappable work ahead of
    the long solve it should hide behind.
    """

    name: str
    tag: str
    inputs: tuple[str, ...]
    outputs: tuple[str, ...]
    fn: Callable
    corrector: int | None = None
    instrumented_fn: Callable | None = None
    probe: Callable | None = None
    probe_inputs: tuple[str, ...] = ()
    probe_iters: str | None = None
    blocking: bool = False

    @property
    def label(self) -> str:
        """Display name, unique per program position."""
        return (self.name if self.corrector is None
                else f"{self.name}[{self.corrector}]")


def _bind(env: dict, phase: Phase, out) -> None:
    """Store a phase's return value(s) under its declared output names."""
    if len(phase.outputs) == 1:
        out = (out,)
    if len(out) != len(phase.outputs):
        raise ValueError(
            f"phase {phase.label} returned {len(out)} values for outputs "
            f"{phase.outputs}")
    env.update(zip(phase.outputs, out))


def _run_phase(env: dict, phase: Phase) -> None:
    """Run one phase of a traced program on ``env`` under
    ``jax.named_scope(phase.name)``: the compiled HLO's ``op_name``
    metadata then names the phase of every instruction it issues (the
    correctors of a phase share one scope).  Metadata only — the
    computation is the same."""
    with jax.named_scope(phase.name):
        _bind(env, phase, phase.fn(*(env[k] for k in phase.inputs)))


def _timed_phase_walk(program: StepProgram, fns: dict, probes: dict,
                      env: dict, n_rows: int) -> list[dict]:
    """Walk the phase list with per-phase wall timers; mutate ``env``.

    THE instrumented walk — the solo and cohort-batched executors both
    call it so the timing/apportioning policy stays a one-place edit.
    Each measured phase wall is shared evenly across ``n_rows`` sessions
    (1 for the solo executor; a cohort stacks same-shape states, so the
    per-session work is identical); returns one tag-times dict per row.

    A probed phase apportions a halo share per row from that row's OWN
    iteration count: the standalone probe pays per-call dispatch the
    fused Krylov loop does not, so it is an upper bound at small sizes —
    never let the estimate claim more than half the measured solve.
    """
    share = 1.0 / n_rows
    t = [dict.fromkeys(PHASE_TAGS, 0.0) for _ in range(n_rows)]
    for ph in program.phases:
        fn = fns[ph.name]
        args = [env[k] for k in ph.inputs]
        if ph.probe is None:
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(*args))
            t_phase = (time.perf_counter() - t0) * share
            _bind(env, ph, out)
            for row in t:
                row[ph.tag] += t_phase
            continue
        # probe one halo exchange to apportion the solve time
        t0 = time.perf_counter()
        jax.block_until_ready(
            probes[ph.name](*(env[k] for k in ph.probe_inputs)))
        t_probe = (time.perf_counter() - t0) * share
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        t_phase = (time.perf_counter() - t0) * share
        _bind(env, ph, out)
        iters = jnp.atleast_1d(env[ph.probe_iters])
        for i, row in enumerate(t):
            halo_est = min(float(iters[i]) * t_probe, 0.5 * t_phase)
            row["halo"] += halo_est
            row[ph.tag] += t_phase - halo_est
    return t


def _memoized_roll(cache: dict, fn: Callable, n_steps: int) -> Callable:
    """The jitted ``lax.scan`` roll of ``fn`` over ``n_steps``, donated
    and memoized per window length (one XLA program per distinct length)
    — shared by the solo and cohort-batched executors.  Extra operands
    beyond ``(state, dt)`` (a padded program's per-session ``n_active``)
    ride along untouched — traced, not donated."""
    n = int(n_steps)
    if n < 1:
        raise ValueError(f"n_steps must be >= 1, got {n_steps}")
    roll = cache.get(n)
    if roll is None:

        def rolled(state, dt, *extra):
            return jax.lax.scan(lambda s, _: fn(s, dt, *extra), state, None,
                                length=n)

        roll = cache[n] = jax.jit(rolled, donate_argnums=(0,))
    return roll


def _converged_outer(program: StepProgram, max_iters: int) -> Callable:
    """The per-session outer loop: iterate the program's step under
    ``lax.while_loop`` until its ``converged`` predicate fires on the
    step stats, capped at ``max_iters``.

    Returns a pure ``(state, dt, *extra) -> (state, stats, n_outer)``
    function (``n_outer`` an int32 scalar — the number of outer
    iterations actually run).  The first step is unrolled so the loop
    carry ``(state, stats, k)`` has concrete stats to test; the caller
    jits (donating the state) and optionally vmaps it — under ``vmap``
    the while-loop's batching rule keeps stepping until every lane's
    predicate drops while *selecting the old carry* for already-converged
    lanes, so each session in a cohort stops at its own iteration count.
    """
    n = int(max_iters)
    if n < 1:
        raise ValueError(f"max_iters must be >= 1, got {max_iters}")
    if program.converged is None:
        raise ValueError(
            "program declares no convergence predicate (converged=None): "
            "run_converged is only meaningful for steady-state programs")
    step = program.as_step_fn()
    conv = program.converged

    def run(state, dt, *extra):
        state, stats = step(state, dt, *extra)

        def cond(carry):
            _, st, k = carry
            return (k < n) & jnp.logical_not(conv(st))

        def body(carry):
            s, _, k = carry
            s, st = step(s, dt, *extra)
            return s, st, k + 1

        return jax.lax.while_loop(
            cond, body, (state, stats, jnp.asarray(1, jnp.int32)))

    return run


@dataclasses.dataclass(frozen=True)
class PipelineForm:
    """A program's software-pipelined alternative schedule.

    ``phases`` is a *restructured* phase list computing the same step as
    the program's serial list but factored so the dependence DAG exposes
    overlap — e.g. PISO splits the pressure assembly into a
    corrector-invariant matrix phase (hoistable next to the momentum
    solve) and a cheap per-corrector source phase.  ``ring`` names env
    keys carried **across the scan step boundary**: each listed key must
    be produced by some phase, and its value at the end of step t feeds
    step t+1's env — software pipelining proper, since XLA cannot CSE
    across ``lax.scan`` iterations.  ``prime`` seeds the ring for the
    first step (the pipeline prologue): ``prime(env) -> {ring key: value}``
    from the seeded env, run once per window *inside* the jitted program.
    """

    phases: tuple[Phase, ...]
    ring: tuple[str, ...] = ()
    prime: Callable | None = None


def _pipeline_schedule(phases: tuple[Phase, ...]):
    """Compile declared phase inputs/outputs into the pipelined schedule.

    Builds the dependence DAG (RAW + WAW + WAR over env keys, in declared
    order — predecessors always have smaller indices), levelizes it, and
    returns ``(schedule, levels, frontier)``:

    * ``schedule`` — the phases re-ordered by ``(level, blocking,
      declared index)``: at each dependence level every independent
      non-blocking phase is issued *before* the blocking Krylov solves,
      so the overlappable work precedes the long latency it hides behind;
    * ``levels`` — the per-phase dependence depth (declared order);
    * ``frontier`` — for each blocking phase, the labels of phases with
      **no transitive dependence either way**: the legal overlap set,
      computed from the declarations alone (the testable artifact).
    """
    n = len(phases)
    last_writer: dict[str, int] = {}
    readers: dict[str, list[int]] = {}
    preds: list[set[int]] = [set() for _ in range(n)]
    for j, ph in enumerate(phases):
        for k in ph.inputs:                       # RAW
            if k in last_writer:
                preds[j].add(last_writer[k])
        for k in ph.outputs:
            if k in last_writer:                  # WAW
                preds[j].add(last_writer[k])
            for r in readers.get(k, ()):          # WAR
                if r != j:
                    preds[j].add(r)
        for k in ph.inputs:
            readers.setdefault(k, []).append(j)
        for k in ph.outputs:
            last_writer[k] = j
            readers[k] = []
    levels: list[int] = []
    for j in range(n):
        levels.append(1 + max((levels[p] for p in preds[j]), default=0))
    order = sorted(range(n),
                   key=lambda j: (levels[j], phases[j].blocking, j))
    anc: list[set[int]] = [set() for _ in range(n)]
    for j in range(n):
        for p in preds[j]:
            anc[j] |= anc[p] | {p}
    frontier = {
        ph.label: tuple(phases[k].label for k in range(n)
                        if k != j and k not in anc[j] and j not in anc[k])
        for j, ph in enumerate(phases) if ph.blocking
    }
    return tuple(phases[j] for j in order), tuple(levels), frontier


def _pipeline_step_fn(program: StepProgram) -> Callable:
    """The pipelined form's pure ``(state, dt, *extras) -> (state, stats)``
    single step: seed, prime the ring (degenerating to the serial
    computation when nothing is carried in), run the scheduled phases,
    finalize.  Ring *outputs* are dead for a lone step — XLA drops them."""
    form = program.pipeline
    schedule, _, _ = _pipeline_schedule(form.phases)
    prime = form.prime

    def step(state, dt, *extra):
        with jax.named_scope("seed"):
            env = program.seed(state, dt, *extra)
        if prime is not None:
            with jax.named_scope("prime"):
                env.update(prime(env))
        for ph in schedule:
            _run_phase(env, ph)
        with jax.named_scope("finalize"):
            return program.finalize(env)

    return step


def _pipeline_rolled_fn(program: StepProgram, n_steps: int) -> Callable:
    """The pipelined window: prologue (prime the ring from the seeded
    env), ``lax.scan`` steady state carrying ``(state, ring)``, implicit
    epilogue (the final ring values are dropped with the last carry).
    One dispatch per window, state donated by the caller's jit —
    identical contract to the fused roll."""
    n = int(n_steps)
    if n < 1:
        raise ValueError(f"n_steps must be >= 1, got {n_steps}")
    form = program.pipeline
    schedule, _, _ = _pipeline_schedule(form.phases)
    ring_keys = form.ring
    prime = form.prime

    def rolled(state, dt, *extra):
        with jax.named_scope("seed"):
            env0 = program.seed(state, dt, *extra)
        with jax.named_scope("prime"):
            primed = prime(env0) if prime is not None else {}
        ring0 = tuple(primed[k] for k in ring_keys)

        def body(carry, _):
            st, ring = carry
            with jax.named_scope("seed"):
                env = program.seed(st, dt, *extra)
            env.update(zip(ring_keys, ring))
            for ph in schedule:
                _run_phase(env, ph)
            with jax.named_scope("finalize"):
                st2, stats = program.finalize(env)
            return (st2, tuple(env[k] for k in ring_keys)), stats

        (state, _), stats = jax.lax.scan(body, (state, ring0), None,
                                         length=n)
        return state, stats

    return rolled


def _require_pipeline(program: StepProgram) -> None:
    if program.pipeline is None:
        raise ValueError(
            "program declares no PipelineForm (pipeline=None): steady "
            "programs (SIMPLE) cannot software-pipeline — their "
            "run_converged while-loop has an unknown trip count, so there "
            "is no static window to carry the ring across")


@dataclasses.dataclass(frozen=True)
class StepProgram:
    """An ordered phase list + env seeding/finalization: one timestep.

    ``seed(state, dt)`` produces the initial env dict (keys declared in
    ``seed_keys``); phases then read/write named env slots in order;
    ``finalize(env)`` folds the final env into ``(state, stats)``.
    Construction validates the dataflow: every phase input must be
    produced by the seed or an earlier phase, every tag must be a
    :class:`PhaseBreakdown` field, and a probe must name one of its
    phase's outputs as the iteration count.
    """

    phases: tuple[Phase, ...]
    seed: Callable
    finalize: Callable
    seed_keys: tuple[str, ...]
    # names of extra per-session operands beyond (state, dt): the seed is
    # called as seed(state, dt, *extras) and every executor entry point
    # accepts the same trailing operands.  A padded (size-class) program
    # declares ("n_active",) — the traced real-part count each session
    # carries so one compiled program serves a whole size class; SIMPLE
    # adds its under-relaxation factors ("relax_u", "relax_p") so two
    # tenants with different factors share one compilation.
    extra_keys: tuple[str, ...] = ()
    # the program's outer-loop convergence predicate: ``stats -> bool``
    # on the per-step stats pytree (a traced scalar under jit).  A
    # steady-state program (SIMPLE) declares one and the executors'
    # ``run_converged`` iterates the step under ``lax.while_loop`` until
    # it fires or an iteration cap is hit; ``None`` (transient programs —
    # PISO) means the program only rolls fixed windows.
    converged: Callable | None = None
    # the program's software-pipelined alternative schedule; ``None``
    # means the program only runs serially (PipelinedExecutor refuses)
    pipeline: PipelineForm | None = None

    def __post_init__(self):
        self._validate_phases(self.phases, set(self.seed_keys))
        if self.pipeline is not None:
            form = self.pipeline
            self._validate_phases(form.phases,
                                  set(self.seed_keys) | set(form.ring))
            produced = set()
            for ph in form.phases:
                produced.update(ph.outputs)
            missing = [k for k in form.ring if k not in produced]
            if missing:
                raise ValueError(
                    f"pipeline ring keys {missing} are not produced by any "
                    f"pipeline phase — nothing to carry across the step "
                    f"boundary")
            if form.ring and form.prime is None:
                raise ValueError(
                    "a pipeline with ring-carried keys needs a prime() "
                    "prologue to seed them for the first step")

    @staticmethod
    def _validate_phases(phases, available: set) -> None:
        """Dataflow validation shared by the serial + pipelined lists."""
        for ph in phases:
            if ph.tag not in PHASE_TAGS:
                raise ValueError(
                    f"phase {ph.label}: unknown tag {ph.tag!r} "
                    f"(must be one of {PHASE_TAGS})")
            missing = [k for k in ph.inputs if k not in available]
            if missing:
                raise ValueError(
                    f"phase {ph.label}: inputs {missing} are neither seeded "
                    f"nor produced by an earlier phase")
            if ph.probe is not None:
                if ph.probe_iters not in ph.outputs:
                    raise ValueError(
                        f"phase {ph.label}: probe_iters {ph.probe_iters!r} "
                        f"is not one of its outputs {ph.outputs}")
                missing = [k for k in ph.probe_inputs if k not in available]
                if missing:
                    raise ValueError(
                        f"phase {ph.label}: probe inputs {missing} not "
                        f"available before the phase")
            available.update(ph.outputs)

    def as_step_fn(self) -> Callable:
        """The pure ``(state, dt, *extras) -> (state, stats)`` composition."""

        def step(state, dt, *extra):
            with jax.named_scope("seed"):
                env = self.seed(state, dt, *extra)
            for ph in self.phases:
                _run_phase(env, ph)
            with jax.named_scope("finalize"):
                return self.finalize(env)

        return step


# ---------------------------------------------------------------------------
# Executor 1: fused (one XLA dispatch per step / per scan-rolled window)
# ---------------------------------------------------------------------------

class FusedExecutor:
    """The program as one jitted XLA executable, with a scan-rolled window.

    ``dt`` is an ordinary traced operand — two different timestep sizes
    share one compilation — and the input state's buffers are donated to
    the output state (same shapes/dtypes, so XLA aliases them in place):
    the caller must keep using the *returned* state.  ``dispatches``
    counts host→XLA executable launches issued through this executor —
    the quantity the scan roll exists to amortize.
    """

    def __init__(self, program: StepProgram):
        self.program = program
        self._fn = program.as_step_fn()
        self._step = jax.jit(self._fn, donate_argnums=(0,))
        self._rolled: dict[int, Callable] = {}
        self._outer: dict[int, Callable] = {}
        self.dispatches = 0

    def step(self, state, dt, *extra):
        """One timestep, one dispatch.  Donates ``state``."""
        self.dispatches += 1
        return self._step(state, dt, *extra)

    def run_steps(self, state, dt, n_steps: int, *extra):
        """``n_steps`` timesteps as ONE dispatch (``lax.scan`` over the
        program); returns ``(state, stats)`` with every ``StepStats`` leaf
        stacked along a leading ``n_steps`` axis.  Donates ``state``.
        Each distinct window length compiles once (memoized)."""
        roll = _memoized_roll(self._rolled, self._fn, n_steps)
        self.dispatches += 1
        return roll(state, dt, *extra)

    def run_converged(self, state, dt, max_iters: int, *extra):
        """Outer-iterate to the program's convergence predicate as ONE
        dispatch (``lax.while_loop`` over the step, capped at
        ``max_iters``).  Returns ``(state, stats, n_outer)`` — the
        last step's stats and the iteration count actually run.
        Donates ``state``; memoized per distinct cap."""
        n = int(max_iters)
        outer = self._outer.get(n)
        if outer is None:
            outer = self._outer[n] = jax.jit(
                _converged_outer(self.program, n), donate_argnums=(0,))
        self.dispatches += 1
        return outer(state, dt, *extra)

    @property
    def trace_count(self) -> int:
        """Compilation-cache entries of the per-step stepper (regression
        meter for the dt-retrace bug; -1 when jax hides the cache)."""
        try:
            return self._step._cache_size()
        except Exception:  # noqa: BLE001 — jax-internal API
            return -1

    def lower_step(self, state, dt, *extra):
        """Lowered+compiled per-step executable (donation/HLO inspection)."""
        return self._step.lower(state, dt, *extra).compile()

    def lower_steps(self, state, dt, n_steps: int, *extra):
        """The compiled window that ``run_steps(state, dt, n_steps)``
        dispatches: the same memoized jit, lowered and compiled (a hit in
        JAX's compilation caches where that window ran).  ``state`` may be
        ``jax.ShapeDtypeStruct`` leaves, so a caller can ask after the
        state was donated."""
        roll = _memoized_roll(self._rolled, self._fn, n_steps)
        return roll.lower(state, dt, *extra).compile()


# ---------------------------------------------------------------------------
# Executor 2: instrumented (per-phase wall timers -> PhaseBreakdown)
# ---------------------------------------------------------------------------

class InstrumentedExecutor:
    """Walk the phase list with per-phase ``block_until_ready`` timers.

    Numerically identical to the fused executor (same phase functions,
    jitted per phase rather than fused); the first call after a program
    build includes trace+compile time, so controllers discard warm-up
    samples (``ControllerConfig.warmup``).  Per-corrector phase instances
    share one jit trace (they share ``fn``); a phase's
    ``instrumented_fn`` override (the plan cache's pooled update) is used
    as-is, already composed of jitted pieces.

    The instrumented walk always FORCES THE SERIAL SCHEDULE — even when
    the program declares a :class:`PipelineForm` and the session advances
    through the pipelined executor.  Per-phase ``block_until_ready`` walls
    are meaningless when phases overlap (the wall of the blocking solve
    would absorb the hidden assembly), so attribution is only defined on
    the serial order; every emitted :class:`PhaseBreakdown` accordingly
    carries ``overlapped=False`` and stays valid for calibrating the
    serial cost model, on top of which the pipelined prediction is a
    ``max()`` (:meth:`repro.core.cost_model.CostModel.T_step_pipelined`).
    """

    def __init__(self, program: StepProgram):
        self.program = program
        self._fns: dict[str, Callable] = {}
        self._probes: dict[str, Callable] = {}
        for ph in program.phases:
            if ph.name not in self._fns:
                self._fns[ph.name] = (ph.instrumented_fn
                                      if ph.instrumented_fn is not None
                                      else jax.jit(ph.fn))
            if ph.probe is not None and ph.name not in self._probes:
                self._probes[ph.name] = jax.jit(ph.probe)
        self.calls = 0

    def timed_step(self, state, dt, *extra):
        """One step; returns ``(state, stats, PhaseBreakdown)``."""
        self.calls += 1
        prog = self.program
        env = prog.seed(state, dt, *extra)
        rows = _timed_phase_walk(prog, self._fns, self._probes, env, 1)
        state, stats = prog.finalize(env)
        return state, stats, PhaseBreakdown(**rows[0])


# ---------------------------------------------------------------------------
# Executor 3: batched (one dispatch per cohort rolled window — vmap over a
# leading session axis)
# ---------------------------------------------------------------------------

class BatchedExecutor:
    """The program vmapped over a leading session (cohort) axis.

    A cohort is a group of same-shape tenants: every ``PisoState`` leaf is
    stacked along a new leading axis of size ``batch`` and ``dt`` becomes a
    ``(batch,)`` vector (``in_axes=(0, 0)`` — each session keeps its own
    timestep size).  ``run_steps`` scan-rolls ``n`` timesteps of the whole
    cohort into ONE XLA dispatch — S tenants advancing a window cost one
    executable launch instead of S — with the stacked state donated exactly
    like the single-session :class:`FusedExecutor`.

    Per-session numerics are the solo program's: ``jax.vmap`` of the
    ``lax.while_loop`` Krylov solves freezes converged lanes (the batched
    body selects the old carry once a lane's predicate drops), so each
    session's iterates and iteration counts match its sequential run.

    ``timed_step`` is the cohort's instrumented sample: it walks the phase
    list vmapped with per-phase ``block_until_ready`` timers and apportions
    each phase wall time **evenly across the cohort** (same shapes ⇒ same
    per-session work), emitting one :class:`PhaseBreakdown` row per session
    — the probed halo share uses each session's own iteration count — so
    every tenant's controller keeps calibrating independently.
    """

    def __init__(self, program: StepProgram, batch: int):
        if batch < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        self.program = program
        self.batch = batch
        # every operand carries a leading session axis: the state pytree,
        # the per-session dt vector, and any extra per-session operands
        # (a padded program's (batch,) n_active vector)
        self._vfn = jax.vmap(program.as_step_fn(), in_axes=0)
        self._step = jax.jit(self._vfn, donate_argnums=(0,))
        self._rolled: dict[int, Callable] = {}
        self._outer: dict[int, Callable] = {}
        self.dispatches = 0
        # the batched instrumented walk: per-phase vmapped jits (shared per
        # phase name, like InstrumentedExecutor; the plan cache's pooled
        # instrumented_fn overrides are unbatched executables, so the
        # batched walk always uses the phase's own fn)
        self._fns: dict[str, Callable] = {}
        self._probes: dict[str, Callable] = {}
        for ph in program.phases:
            if ph.name not in self._fns:
                self._fns[ph.name] = jax.jit(jax.vmap(ph.fn))
            if ph.probe is not None and ph.name not in self._probes:
                self._probes[ph.name] = jax.jit(jax.vmap(ph.probe))
        self._seed = jax.vmap(program.seed)
        self._finalize = jax.jit(jax.vmap(program.finalize))
        self.samples = 0

    def _check(self, states, dts, extras) -> None:
        lead = jax.tree.leaves(states)[0].shape[0]
        if lead != self.batch or dts.shape != (self.batch,):
            raise ValueError(
                f"cohort shape mismatch: executor batch={self.batch}, "
                f"state lead={lead}, dt shape={dts.shape}")
        for name, x in zip(self.program.extra_keys, extras):
            if jax.tree.leaves(x)[0].shape[:1] != (self.batch,):
                raise ValueError(
                    f"cohort extra {name!r} must carry a leading "
                    f"({self.batch},) session axis")

    def step(self, states, dts, *extras):
        """One timestep for the whole cohort, one dispatch.  Donates
        ``states``; ``dts`` is the per-session ``(batch,)`` vector."""
        self._check(states, dts, extras)
        self.dispatches += 1
        return self._step(states, dts, *extras)

    def run_steps(self, states, dts, n_steps: int, *extras):
        """``n_steps`` cohort timesteps as ONE dispatch.  Returns
        ``(states, stats)`` with every ``StepStats`` leaf carrying leading
        ``(n_steps, batch)`` axes.  Donates ``states``; each distinct
        window length compiles once per cohort shape."""
        self._check(states, dts, extras)
        roll = _memoized_roll(self._rolled, self._vfn, n_steps)
        self.dispatches += 1
        return roll(states, dts, *extras)

    def run_converged(self, states, dts, max_iters: int, *extras):
        """The whole cohort outer-iterated to convergence as ONE dispatch.

        ``jax.vmap`` of the per-session while loop: the batched predicate
        keeps the loop alive until every lane converges (or hits the
        cap), with already-converged lanes frozen by the while-loop
        batching rule's carry select — each session's final state and
        ``n_outer`` match its solo ``FusedExecutor.run_converged`` run.
        Returns ``(states, stats, n_outer)`` with ``n_outer`` a
        ``(batch,)`` int32 vector.  Donates ``states``.
        """
        self._check(states, dts, extras)
        n = int(max_iters)
        outer = self._outer.get(n)
        if outer is None:
            outer = self._outer[n] = jax.jit(
                jax.vmap(_converged_outer(self.program, n)),
                donate_argnums=(0,))
        self.dispatches += 1
        return outer(states, dts, *extras)

    def timed_step(self, states, dts, *extras):
        """One instrumented cohort step.

        Returns ``(states, stats, rows)``: the stacked next state, the
        stacked per-session ``StepStats``, and one apportioned
        :class:`PhaseBreakdown` per session (``len(rows) == batch``).
        Does NOT donate ``states``.
        """
        self._check(states, dts, extras)
        self.samples += 1
        env = self._seed(states, dts, *extras)
        rows = _timed_phase_walk(self.program, self._fns, self._probes,
                                 env, self.batch)
        states, stats = self._finalize(env)
        return states, stats, [PhaseBreakdown(**row) for row in rows]


# ---------------------------------------------------------------------------
# Executor 4: software-pipelined (the PipelineForm schedule, ring-carried
# across the scan step boundary) + its cohort (vmapped) variant
# ---------------------------------------------------------------------------

class PipelinedExecutor:
    """The program's :class:`PipelineForm` as one jitted XLA executable.

    Same external contract as :class:`FusedExecutor` — ``dt`` traced,
    state donated, ``run_steps`` rolls a window into ONE ``lax.scan``
    dispatch with stacked ``StepStats`` — but the body runs the
    *pipelined* schedule: phases re-ordered along the computed dependence
    levels (independent work hoisted ahead of the blocking solves) and
    ``ring``-carried values crossing the step boundary, so step t+1's
    assembly consumes a value produced while step t was still solving
    (the prologue primes the ring; the epilogue simply drops the last
    carry).  ``schedule``/``levels``/``frontier`` expose the compiled
    overlap structure for tests and docs.

    ``run_converged`` refuses: a steady program's while-loop trip count
    is unknown at trace time, so there is no static window to pipeline
    across (those programs keep the serial executors).
    """

    def __init__(self, program: StepProgram):
        _require_pipeline(program)
        self.program = program
        self.schedule, self.levels, self.frontier = _pipeline_schedule(
            program.pipeline.phases)
        self._fn = _pipeline_step_fn(program)
        self._step = jax.jit(self._fn, donate_argnums=(0,))
        self._rolled: dict[int, Callable] = {}
        self.dispatches = 0

    def step(self, state, dt, *extra):
        """One timestep, one dispatch.  Donates ``state``."""
        self.dispatches += 1
        return self._step(state, dt, *extra)

    def _roll(self, n_steps: int) -> Callable:
        n = int(n_steps)
        roll = self._rolled.get(n)
        if roll is None:
            roll = self._rolled[n] = jax.jit(
                _pipeline_rolled_fn(self.program, n), donate_argnums=(0,))
        return roll

    def run_steps(self, state, dt, n_steps: int, *extra):
        """``n_steps`` pipelined timesteps as ONE dispatch; stacked
        ``StepStats``; donates ``state``; memoized per window length."""
        roll = self._roll(n_steps)
        self.dispatches += 1
        return roll(state, dt, *extra)

    def run_converged(self, state, dt, max_iters: int, *extra):
        raise ValueError(
            "PipelinedExecutor cannot run_converged: the convergence "
            "while-loop's trip count is unknown at trace time, so there is "
            "no static window to software-pipeline across — use the fused "
            "executor for steady outer iteration")

    @property
    def trace_count(self) -> int:
        """Compilation-cache entries of the per-step stepper (dt-retrace
        regression meter; -1 when jax hides the cache)."""
        try:
            return self._step._cache_size()
        except Exception:  # noqa: BLE001 — jax-internal API
            return -1

    def lower_step(self, state, dt, *extra):
        """Lowered+compiled per-step executable (donation/HLO inspection)."""
        return self._step.lower(state, dt, *extra).compile()

    def lower_steps(self, state, dt, n_steps: int, *extra):
        """The compiled window that ``run_steps(state, dt, n_steps)``
        dispatches (see :meth:`FusedExecutor.lower_steps`)."""
        return self._roll(n_steps).lower(state, dt, *extra).compile()


class BatchedPipelinedExecutor:
    """The pipelined schedule vmapped over a leading session axis.

    A cohort's window is ONE dispatch of the vmapped pipelined roll —
    each lane carries its own ring (primed per lane inside the vmap), so
    per-session numerics match the solo :class:`PipelinedExecutor`.
    ``timed_step`` deliberately DELEGATES to a serial
    :class:`BatchedExecutor` walk: per-phase walls are meaningless under
    an overlapped schedule, so instrumented samples always measure the
    serial form (and emit ``overlapped=False`` rows the controller may
    calibrate from).
    """

    def __init__(self, program: StepProgram, batch: int):
        _require_pipeline(program)
        self.program = program
        self.batch = batch
        # the serial batched executor validates batch >= 1 and provides
        # the cohort shape check + the serial instrumented walk
        self._serial = BatchedExecutor(program, batch)
        self._vfn = jax.vmap(_pipeline_step_fn(program), in_axes=0)
        self._step = jax.jit(self._vfn, donate_argnums=(0,))
        self._rolled: dict[int, Callable] = {}
        self.dispatches = 0
        self.samples = 0

    def step(self, states, dts, *extras):
        """One pipelined cohort timestep, one dispatch.  Donates
        ``states``; ``dts`` is the per-session ``(batch,)`` vector."""
        self._serial._check(states, dts, extras)
        self.dispatches += 1
        return self._step(states, dts, *extras)

    def run_steps(self, states, dts, n_steps: int, *extras):
        """``n_steps`` pipelined cohort timesteps as ONE dispatch;
        ``StepStats`` leaves carry leading ``(n_steps, batch)`` axes;
        donates ``states``; memoized per window length."""
        self._serial._check(states, dts, extras)
        n = int(n_steps)
        roll = self._rolled.get(n)
        if roll is None:
            vroll = jax.vmap(_pipeline_rolled_fn(self.program, n), in_axes=0)

            def rolled(states, dts, *extras):
                out, stats = vroll(states, dts, *extras)
                # the scan runs inside the vmap, so stats leaves come out
                # (batch, n_steps, ...); swap to the serial cohort
                # convention (n_steps, batch, ...) the engine indexes by
                stats = jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1), stats)
                return out, stats

            roll = self._rolled[n] = jax.jit(rolled, donate_argnums=(0,))
        self.dispatches += 1
        return roll(states, dts, *extras)

    def run_converged(self, states, dts, max_iters: int, *extras):
        raise ValueError(
            "BatchedPipelinedExecutor cannot run_converged — see "
            "PipelinedExecutor.run_converged")

    def timed_step(self, states, dts, *extras):
        """One instrumented cohort step — on the SERIAL schedule (the
        pipelined walls overlap and cannot be attributed per phase).
        Returns ``(states, stats, rows)`` with ``overlapped=False`` rows.
        Does NOT donate ``states``."""
        self.samples += 1
        return self._serial.timed_step(states, dts, *extras)


class ProgramExecutors:
    """The compiled artifacts of one program binding (memoized per
    ``(alpha, solve_mode, solver_backend, pipelined)`` by ``PisoSolver``).
    Batched executors are additionally memoized per cohort size — each
    cohort shape is its own set of XLA programs and its own dispatch
    counter.  The pipelined executors are built lazily: a program without
    a :class:`PipelineForm` (SIMPLE) raises only if someone actually asks
    for them."""

    def __init__(self, program: StepProgram):
        self.program = program
        self.fused = FusedExecutor(program)
        self.instrumented = InstrumentedExecutor(program)
        self._batched: dict[int, BatchedExecutor] = {}
        self._pipelined: PipelinedExecutor | None = None
        self._batched_pipelined: dict[int, BatchedPipelinedExecutor] = {}

    def batched(self, batch: int) -> BatchedExecutor:
        """The cohort executor for ``batch`` stacked sessions (memoized)."""
        exe = self._batched.get(batch)
        if exe is None:
            exe = self._batched[batch] = BatchedExecutor(self.program, batch)
        return exe

    @property
    def pipelined(self) -> PipelinedExecutor:
        """The software-pipelined executor (lazy; raises for programs
        without a :class:`PipelineForm`)."""
        if self._pipelined is None:
            self._pipelined = PipelinedExecutor(self.program)
        return self._pipelined

    def batched_pipelined(self, batch: int) -> BatchedPipelinedExecutor:
        """The pipelined cohort executor for ``batch`` sessions (memoized,
        lazy like :attr:`pipelined`)."""
        exe = self._batched_pipelined.get(batch)
        if exe is None:
            exe = self._batched_pipelined[batch] = BatchedPipelinedExecutor(
                self.program, batch)
        return exe


def roll_schedule(start: int, n_steps: int, every: int | None,
                  cap: int | None = None):
    """Yield the engine executor's cadence: ``(is_sample, chunk)`` stretches.

    The sampling grid is anchored at the *absolute* step index ``start``
    (step indices divisible by ``every`` are instrumented samples), so the
    cadence is stable across repeated requests; ``every=None`` never
    samples (a non-adaptive run is pure rolled windows).  Non-sample
    stretches run to the next sample point, optionally capped at ``cap``
    steps per rolled dispatch — the cap bounds both compile-cache growth
    (one ``lax.scan`` program per distinct window length) and the stats
    buffer of a single window.  Shared by
    ``SimulationEngine.step_session`` and the adaptive
    ``repro.launch.cavity`` loop so the two drivers cannot drift.
    """
    if every is not None and every < 1:
        raise ValueError("every must be >= 1")
    done = 0
    while done < n_steps:
        step = start + done
        if every is not None and step % every == 0:
            yield True, 1
            done += 1
            continue
        chunk = n_steps - done
        if every is not None:
            chunk = min(every - step % every, chunk)
        if cap is not None:
            chunk = min(chunk, cap)
        yield False, chunk
        done += chunk


# ---------------------------------------------------------------------------
# The program registry: timestep programs as first-class artifacts
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """Registry entry for a timestep program.

    ``build(solver)`` binds a :class:`SegregatedSolver`'s plans +
    SolverOps into a :class:`StepProgram`; ``transient`` distinguishes
    time-marching programs (PISO — roll fixed windows) from steady-state
    ones (SIMPLE — outer-iterate to ``converged``).  Mirrors the flow-case
    registry (``fvm/cases.py``): the *name* is what solver bindings,
    serving cohort keys and benchmark cells thread around.
    """

    name: str
    build: Callable
    transient: bool = True
    description: str = ""
    # whether the built program declares a PipelineForm: the STATIC half
    # of the solver's pipeline=auto|on|off resolution (known before the
    # program is built, so it can key the executor memoization).  A
    # steady-state program must leave this False — run_converged cannot
    # software-pipeline across an unknown trip count.
    pipelined: bool = False


PROGRAMS: dict[str, ProgramSpec] = {}


def register_program(spec: ProgramSpec) -> ProgramSpec:
    if spec.name in PROGRAMS:
        raise ValueError(f"program {spec.name!r} already registered")
    PROGRAMS[spec.name] = spec
    return spec


def program_names() -> tuple[str, ...]:
    get_program("simple")  # force the lazy registration
    return tuple(sorted(PROGRAMS))


def get_program(name: str) -> ProgramSpec:
    """Look up a registered program spec by name.

    ``repro.fvm.simple`` registers on import; it is imported lazily here
    (it imports this module) so ``SimpleSolver`` users never need to
    touch it directly.
    """
    if name not in PROGRAMS:
        import importlib
        try:
            importlib.import_module("repro.fvm.simple")
        except ImportError:
            pass
    try:
        return PROGRAMS[name]
    except KeyError:
        raise KeyError(f"unknown program {name!r} "
                       f"(registered: {tuple(sorted(PROGRAMS))})") from None


# ---------------------------------------------------------------------------
# Compiled health signals
# ---------------------------------------------------------------------------

def health_flags(state, solver_ok, solver_cap, *scalars):
    """Reduce a step's health to three scalar flags, inside the trace.

    ``finite`` is an ``isfinite`` all-reduce over every state leaf plus any
    extra per-step scalars (residuals, continuity error) — one boolean word
    per step, carried through the scan-rolled window like any other stat,
    so supervision costs no extra host syncs.  Returns
    ``(converged, diverged, hit_cap)``: ``converged`` means every Krylov
    solve met its tolerance AND the state is finite; ``diverged`` means a
    non-finite leaf appeared; ``hit_cap`` means some solve exited at
    ``maxiter`` on an otherwise finite state (the three are disjoint-ish:
    a NaN state makes the Krylov conds exit immediately, so ``solver_cap``
    stays False under divergence)."""
    flags = [jnp.all(jnp.isfinite(leaf)) for leaf in jax.tree.leaves(state)]
    flags += [jnp.all(jnp.isfinite(s)) for s in scalars]
    finite = functools.reduce(jnp.logical_and, flags)
    return solver_ok & finite, ~finite, solver_cap & finite


# ---------------------------------------------------------------------------
# The shared phase toolkit (PISO + SIMPLE bind the same phase functions)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PhaseToolkit:
    """The segregated-scheme phase functions bound to one solver.

    Both registered programs are *phase lists over the same phases*
    (Tomczak et al., arXiv:1207.1571): momentum assembly/solve, the
    pressure equation, the conservative flux correction.
    :func:`build_piso_program` and ``repro.fvm.simple``'s builder draw
    from this one binding so a phase-function fix lands in both."""

    asm: object
    padded: bool
    mask_keys: tuple[str, ...]
    asm_of: Callable            # (*masks) -> assembly view
    assemble_mom: Callable
    update_mom: Callable
    solve_mom: Callable
    assemble_p: Callable
    update_p: Callable
    solve_p: Callable
    halo_probe: Callable
    update_mom_inst: Callable | None
    update_p_inst: Callable | None
    # the pipelined form's factored phases: momentum assembly consuming a
    # ring-carried grad(p); the corrector-invariant pressure-matrix half;
    # the per-corrector source-only half; the standalone gradient (ring
    # producer / prologue prime)
    assemble_mom_g: Callable | None = None
    assemble_p_mat: Callable | None = None
    assemble_p_src: Callable | None = None
    grad_p: Callable | None = None


def _phase_toolkit(solver) -> PhaseToolkit:
    """Bind the shared phase functions to a solver's plans + SolverOps."""
    from repro.core.ldu import buffer_from_parts
    from repro.fvm.piso import _offdiag3
    from repro.solvers.bicgstab import BiCGStabResult, bicgstab
    from repro.solvers.cg import cg
    from repro.sparse.distributed import x_pad

    asm = solver.asm
    plan_m, plan_p = solver.plan_mom, solver.plan_p
    n_c = solver.n_coarse
    mom_tol, p_tol = solver.mom_tol, solver.p_tol
    mom_maxiter = getattr(solver, "mom_maxiter", 500)
    p_maxiter = getattr(solver, "p_maxiter", 2000)
    padded = getattr(solver, "padded", False)

    # the activity-mask binding: a padded program threads per-session
    # (traced) masks through the env; a plain program uses the assembly's
    # static masks and keeps the historical (state, dt) step signature
    mask_keys = ("if_mask", "patch_mask") if padded else ()

    def _asm_of(*masks):
        return asm.with_masks(*masks) if masks else asm

    # -- momentum predictor (fine partition, BiCGStab, Jacobi) ------------
    def assemble_mom(U, phi, phi_if, phi_b, p, dt, *masks):
        return _asm_of(*masks).assemble_momentum(U, phi, phi_if, p, dt,
                                                 phi_b=phi_b)

    def update_mom(sysM):
        return solver._bands(plan_m, sysM.diag, sysM.upper, sysM.lower,
                             sysM.iface)

    def solve_mom(bandsM, sysM, U):
        opsM = solver._solver_ops(plan_m, bandsM, sysM.diag)
        res = jax.vmap(
            lambda b, x0: bicgstab(opsM, b, x0, tol=mom_tol,
                                   maxiter=mom_maxiter),
            in_axes=(2, 2),
            out_axes=BiCGStabResult(x=2, iters=0, residual=0,
                                    converged=0, hit_cap=0),
        )(sysM.source, U)
        return (res.x, jnp.max(res.iters),
                jnp.all(res.converged), jnp.any(res.hit_cap))

    # -- the pressure equation --------------------------------------------
    def assemble_p(sysM, U, *masks):
        a = _asm_of(*masks)
        rAU = a.V / sysM.diag
        HbyA = (sysM.source - _offdiag3(a, sysM, U)) / sysM.diag[..., None]
        phiH, phiH_if = a.face_flux(HbyA)
        phiH_b = a.boundary_flux(HbyA)
        sysP = a.assemble_pressure(rAU, phiH, phiH_if, phiH_b)
        return rAU, HbyA, phiH, phiH_if, phiH_b, sysP

    def update_p(sysP):
        return solver._solve_constraint(
            solver._bands(plan_p, sysP.diag, sysP.upper, sysP.lower,
                          sysP.iface))

    def solve_p(bandsP, sysP, p):
        b_c = solver._solve_constraint(sysP.source.reshape(n_c, -1))
        x0_c = solver._solve_constraint(p.reshape(n_c, -1))
        diag_c = sysP.diag.reshape(n_c, -1)
        opsP = solver._solver_ops(plan_p, bandsP, diag_c)
        sol = cg(opsP, b_c, x0_c, tol=p_tol, maxiter=p_maxiter)
        return (sol.x.reshape(p.shape), sol.iters, sol.residual,
                sol.converged, sol.hit_cap, sol.outer_iters)

    def halo_probe(p):
        return x_pad(p.reshape(n_c, -1), plan_p.plane)

    # -- the pipelined form's factored phases ------------------------------
    def assemble_mom_g(U, phi, phi_if, phi_b, gradp, dt, *masks):
        # the ring-carried grad(p) replaces the in-phase gradient — the
        # dataflow edge from step t's last corrector into step t+1
        return _asm_of(*masks).assemble_momentum(U, phi, phi_if, None, dt,
                                                 phi_b=phi_b, gradp=gradp)

    def assemble_p_mat(sysM, *masks):
        # corrector-invariant: every pressure-matrix coefficient depends
        # only on rAU = V / diag(momentum) — build it once per step, next
        # to the momentum solve it is independent of
        a = _asm_of(*masks)
        rAU = a.V / sysM.diag
        return rAU, a.assemble_pressure_matrix(rAU)

    def assemble_p_src(sysM, sysP_mat, rAU, U, *masks):
        # per-corrector: only the divergence source changes with U
        a = _asm_of(*masks)
        HbyA = (sysM.source - _offdiag3(a, sysM, U)) / sysM.diag[..., None]
        phiH, phiH_if = a.face_flux(HbyA)
        phiH_b = a.boundary_flux(HbyA)
        sysP = dataclasses.replace(
            sysP_mat, source=-a.divergence(phiH, phiH_if, phiH_b))
        return HbyA, phiH, phiH_if, phiH_b, sysP

    def grad_p(p, *masks):
        return _asm_of(*masks).grad(p)

    # -- plan-cache hook: pooled compiled updates (instrumented path only) -
    update_mom_inst = update_p_inst = None
    if solver.plan_cache is not None:
        # the gather executable is shared by every solver/session whose
        # plan has the same shape signature (PlanCache.pool)
        pool = solver.plan_cache.pool

        def group(plan, sys):
            buffers = buffer_from_parts(sys.diag, sys.upper, sys.lower,
                                        sys.iface)
            n = buffers.shape[0] // plan.alpha
            return buffers.reshape(n, plan.alpha, plan.buffer_len)

        pooled_m = pool.updater(plan_m, "dia", solver.update_schedule)
        pooled_p = pool.updater(plan_p, "dia", solver.update_schedule)
        group_m = jax.jit(functools.partial(group, plan_m))
        group_p = jax.jit(functools.partial(group, plan_p))
        constrain = (jax.jit(solver._solve_constraint)
                     if solver.spmd_mesh is not None else (lambda x: x))

        def update_mom_inst(sysM):
            return pooled_m(group_m(sysM))

        def update_p_inst(sysP):
            return constrain(pooled_p(group_p(sysP)))

    return PhaseToolkit(
        asm=asm, padded=padded, mask_keys=mask_keys, asm_of=_asm_of,
        assemble_mom=assemble_mom, update_mom=update_mom,
        solve_mom=solve_mom, assemble_p=assemble_p, update_p=update_p,
        solve_p=solve_p, halo_probe=halo_probe,
        update_mom_inst=update_mom_inst, update_p_inst=update_p_inst,
        assemble_mom_g=assemble_mom_g, assemble_p_mat=assemble_p_mat,
        assemble_p_src=assemble_p_src, grad_p=grad_p)


# ---------------------------------------------------------------------------
# The PISO program
# ---------------------------------------------------------------------------

def build_piso_program(solver) -> StepProgram:
    """Bind a ``PisoSolver``'s plans + SolverOps into the PISO phase list.

    Phases close over the solver's *current* plans and SPMD mesh; the
    solver memoizes the built program (and its executors) per
    ``(alpha, solve_mode, solver_backend)``, so a rebind to a new alpha
    builds a fresh program while a revisited alpha reuses trace + XLA
    work.  The phase order is the paper's fig. 5/7 decomposition:
    ``assemble_mom → update_mom → solve_mom`` then, per corrector,
    ``assemble_p → update_p → solve_p → correct``.

    A solver bound to a size-class :class:`~repro.fvm.mesh.PaddedCavityMesh`
    (``solver.padded``) builds the **padded** program: the step takes one
    extra traced operand ``n_active`` (the session's real slab count), the
    seed derives the interface/patch activity masks from it
    (:meth:`~repro.fvm.assembly.CavityAssembly.dynamic_masks`), and the
    assembly phases consume those masks instead of the static ones — so
    ONE compiled (and vmapped) program serves every session of the size
    class, whatever its real mesh size.  Ghost slabs stay exactly zero:
    masked interfaces decouple them, their Krylov residual rows are 0, and
    every global reduction they join gains only exact zeros.
    """
    from repro.fvm.piso import PisoState, StepStats

    tk = _phase_toolkit(solver)
    asm, mask_keys = tk.asm, tk.mask_keys
    n_corr = solver.n_correctors
    if n_corr < 1:
        raise ValueError("the PISO program needs at least one corrector")

    def correct(sysP, phiH, phiH_if, phiH_b, p, HbyA, rAU, *masks):
        a = tk.asm_of(*masks)
        phi, phi_if = a.correct_flux(sysP, phiH, phiH_if, p)
        phi_b = a.correct_boundary_flux(sysP, phiH_b, p)
        U = HbyA - rAU[..., None] * a.grad(p)
        cont = jnp.max(jnp.abs(a.divergence(phi, phi_if, phi_b))) / a.V
        return phi, phi_if, phi_b, U, cont

    # phase attribution follows the paper's two partitions: the whole
    # fine-partition share (momentum predictor incl. its BiCGStab solve,
    # pressure assembly, corrections) bills to "assembly"; the coefficient
    # update into the coarse plan to "update"; the coarse pressure CG to
    # "solve" with its probed per-iteration exchange share on "halo"
    phases = [
        Phase("assemble_mom", "assembly",
              ("U", "phi", "phi_if", "phi_b", "p", "dt") + mask_keys,
              ("sysM",), tk.assemble_mom),
        Phase("update_mom", "assembly", ("sysM",), ("bandsM",),
              tk.update_mom, instrumented_fn=tk.update_mom_inst),
        Phase("solve_mom", "assembly", ("bandsM", "sysM", "U"),
              ("U", "mom_iters", "mom_ok", "mom_cap"), tk.solve_mom),
    ]
    for i in range(n_corr):
        phases += [
            Phase("assemble_p", "assembly", ("sysM", "U") + mask_keys,
                  ("rAU", "HbyA", "phiH", "phiH_if", "phiH_b", "sysP"),
                  tk.assemble_p, corrector=i),
            Phase("update_p", "update", ("sysP",), ("bandsP",), tk.update_p,
                  corrector=i, instrumented_fn=tk.update_p_inst),
            Phase("solve_p", "solve", ("bandsP", "sysP", "p"),
                  ("p", f"p_iters_{i}", "p_res", f"p_ok_{i}", f"p_cap_{i}",
                   f"p_outer_{i}"),
                  tk.solve_p, corrector=i,
                  probe=tk.halo_probe, probe_inputs=("p",),
                  probe_iters=f"p_iters_{i}"),
            Phase("correct", "assembly",
                  ("sysP", "phiH", "phiH_if", "phiH_b", "p", "HbyA", "rAU")
                  + mask_keys,
                  ("phi", "phi_if", "phi_b", "U", "cont"), correct,
                  corrector=i),
        ]

    if tk.padded:
        def seed(state, dt, n_active):
            U, p, phi, phi_if, phi_b = state
            if_mask, patch_mask = asm.dynamic_masks(n_active)
            return {"U": U, "p": p, "phi": phi, "phi_if": phi_if,
                    "phi_b": phi_b, "dt": dt, "n_active": n_active,
                    "if_mask": if_mask, "patch_mask": patch_mask}

        seed_keys = ("U", "p", "phi", "phi_if", "phi_b", "dt", "n_active",
                     "if_mask", "patch_mask")
        extra_keys = ("n_active",)
    else:
        def seed(state, dt):
            U, p, phi, phi_if, phi_b = state
            return {"U": U, "p": p, "phi": phi, "phi_if": phi_if,
                    "phi_b": phi_b, "dt": dt}

        seed_keys = ("U", "p", "phi", "phi_if", "phi_b", "dt")
        extra_keys = ()

    def finalize(env):
        state = PisoState(env["U"], env["p"], env["phi"], env["phi_if"],
                          env["phi_b"])
        ok = env["mom_ok"]
        cap = env["mom_cap"]
        for i in range(n_corr):
            ok = ok & env[f"p_ok_{i}"]
            cap = cap | env[f"p_cap_{i}"]
        converged, diverged, hit_cap = health_flags(
            state, ok, cap, env["cont"], env["p_res"])
        stats = StepStats(
            mom_iters=env["mom_iters"],
            p_iters=jnp.stack([env[f"p_iters_{i}"] for i in range(n_corr)]),
            p_outer=jnp.stack([env[f"p_outer_{i}"] for i in range(n_corr)]),
            continuity_err=env["cont"],
            p_residual=env["p_res"],
            converged=converged, diverged=diverged, hit_cap=hit_cap)
        return state, stats

    # ---- the pipelined form ------------------------------------------------
    # The same step, factored so the dependence DAG exposes overlap:
    #  * assemble_mom consumes a RING-CARRIED grad(p) (produced by the
    #    trailing grad_p phase of the PREVIOUS scan iteration — XLA cannot
    #    CSE across scan steps, so the serial form pays that gradient twice
    #    per step boundary; grad_p itself CSEs with correct[last]'s
    #    internal gradient, so the pipelined body pays it once);
    #  * the pressure matrix (and its Jacobi bands via update_p) is built
    #    ONCE per step from rAU only — scheduled next to the momentum
    #    solve, which it does not depend on (the overlap frontier);
    #  * each corrector then re-assembles only the divergence source.
    pipe_phases = [
        Phase("assemble_mom", "assembly",
              ("U", "phi", "phi_if", "phi_b", "gradp", "dt") + mask_keys,
              ("sysM",), tk.assemble_mom_g),
        Phase("update_mom", "assembly", ("sysM",), ("bandsM",),
              tk.update_mom, instrumented_fn=tk.update_mom_inst),
        Phase("solve_mom", "assembly", ("bandsM", "sysM", "U"),
              ("U", "mom_iters", "mom_ok", "mom_cap"), tk.solve_mom,
              blocking=True),
        Phase("assemble_p_mat", "assembly", ("sysM",) + mask_keys,
              ("rAU", "sysP_mat"), tk.assemble_p_mat),
        Phase("update_p", "update", ("sysP_mat",), ("bandsP",),
              tk.update_p, instrumented_fn=tk.update_p_inst),
    ]
    for i in range(n_corr):
        pipe_phases += [
            Phase("assemble_p", "assembly",
                  ("sysM", "sysP_mat", "rAU", "U") + mask_keys,
                  ("HbyA", "phiH", "phiH_if", "phiH_b", "sysP"),
                  tk.assemble_p_src, corrector=i),
            Phase("solve_p", "solve", ("bandsP", "sysP", "p"),
                  ("p", f"p_iters_{i}", "p_res", f"p_ok_{i}", f"p_cap_{i}",
                   f"p_outer_{i}"),
                  tk.solve_p, corrector=i, blocking=True,
                  probe=tk.halo_probe, probe_inputs=("p",),
                  probe_iters=f"p_iters_{i}"),
            Phase("correct", "assembly",
                  ("sysP", "phiH", "phiH_if", "phiH_b", "p", "HbyA", "rAU")
                  + mask_keys,
                  ("phi", "phi_if", "phi_b", "U", "cont"), correct,
                  corrector=i),
        ]
    pipe_phases.append(
        Phase("grad_p", "assembly", ("p",) + mask_keys, ("gradp",),
              tk.grad_p))

    def prime(env):
        # pipeline prologue: the first step's gradient from the seeded p,
        # inside the jitted window (no extra dispatch)
        masks = tuple(env[k] for k in mask_keys)
        return {"gradp": tk.grad_p(env["p"], *masks)}

    pipeline = PipelineForm(phases=tuple(pipe_phases), ring=("gradp",),
                            prime=prime)

    return StepProgram(phases=tuple(phases), seed=seed, finalize=finalize,
                       seed_keys=seed_keys, extra_keys=extra_keys,
                       pipeline=pipeline)


register_program(ProgramSpec(
    name="piso",
    build=build_piso_program,
    transient=True,
    pipelined=True,
    description=("transient PISO: momentum predictor + n_correctors "
                 "pressure corrections per timestep (the paper's fig. 5/7 "
                 "decomposition), with a software-pipelined form "
                 "(ring-carried grad(p), hoisted pressure matrix)"),
))
