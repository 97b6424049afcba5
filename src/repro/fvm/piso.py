"""icoFOAM PISO time loop over the repartitioned distributed system.

Faithful to the paper's measured configuration (§4):

* the **momentum** predictor is solved on the **fine** (CPU/assembly)
  partition with BiCGStab — "OpenFOAM's native BiCGStab" (an alpha=1
  repartition plan, i.e. the identity repartition, gives the fine-partition
  DIA matrix);
* the **pressure** equation is repartitioned with ratio **alpha** onto the
  coarse (GPU/solve) partition and solved with CG — "Ginkgo's CG";
* each PISO corrector re-sends the coefficients through the update pattern
  (paper fig. 3b) — the create/update split means no symbolic work per step.

The timestep itself is declared ONCE as a :class:`~repro.fvm.step_program.
StepProgram` phase list (``assemble_mom → update_mom → solve_mom`` then per
corrector ``assemble_p → update_p → solve_p → correct``) and compiled three
ways from that single definition — fused one-dispatch (``step`` /
scan-rolled ``run_steps``), per-phase instrumented (``timed_step``, the
adaptive controller's feedback), and the serving engine's sampled mix.

:class:`SegregatedSolver` is the case- and program-agnostic *binder*: it
owns the plans, the SolverOps backend dispatch and the SPMD layout
constraints, binds a :class:`~repro.fvm.cases.FlowCase` BC set into the
assembly, builds the registered program named by ``program_name``
(``fvm/step_program.PROGRAMS``), and memoizes the built program +
executors per ``(program, alpha, solve_mode, solver_backend)``.
:class:`PisoSolver` and :class:`SimpleSolver` are thin registered
specializations — the transient PISO marcher and the steady-state
under-relaxed SIMPLE iterator (``run_steady``).

Under pjit the part axes are sharded and the halo exchanges/reductions
lower to collectives.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.ldu import buffer_from_parts
from repro.core.repartition import RepartitionPlan, plan_for_mesh
from repro.core.update import update_device_direct, update_host_buffer
from repro.fvm.assembly import CavityAssembly
from repro.fvm.cases import FlowCase, get_case
from repro.fvm.mesh import CavityMesh
from repro.fvm.step_program import ProgramExecutors, get_program
from repro.solvers.precision import get_policy
from repro.solvers.ops import (fused_stacked_ops, reference_ops,
                               resolve_backend, stacked_reference_ops)

__all__ = ["SegregatedSolver", "PisoSolver", "SimpleSolver", "SOLVERS",
           "make_solver", "PisoState", "StepStats", "stack_states",
           "unstack_states"]


class PisoState(NamedTuple):
    U: jax.Array       # (P, m, 3)
    p: jax.Array       # (P, m)
    phi: jax.Array     # (P, F) conservative face fluxes
    phi_if: jax.Array  # (P, 2, B)
    phi_b: jax.Array   # (P, 2, B) z-boundary fluxes (zero for the cavity)


class StepStats(NamedTuple):
    mom_iters: jax.Array
    p_iters: jax.Array        # (n_correctors,)
    # (n_correctors,): the f64 refinement passes of each pressure solve
    # (CGResult.outer_iters; 0 on the f64 policy)
    p_outer: jax.Array
    continuity_err: jax.Array  # max |div(phi)| after correction
    p_residual: jax.Array
    # compiled health signals (step_program.health_flags): every Krylov
    # solve met tolerance on a finite state / a non-finite leaf appeared /
    # some solve exited at maxiter — one bool word each, no host syncs
    converged: jax.Array
    diverged: jax.Array
    hit_cap: jax.Array


def stack_states(states, pad_to: int | None = None) -> PisoState:
    """Stack per-session ``PisoState``s along a new leading session axis.

    The cohort form consumed by the batched stepper
    (:class:`~repro.fvm.step_program.BatchedExecutor`): every leaf of the
    S input states becomes one ``(S, ...)`` array.  All states must share
    leaf shapes/dtypes (same mesh decomposition — the cohort contract).

    ``pad_to`` appends all-zero **filler lanes** until the leading axis
    reaches that size, so a cohort can ride a lane-class compiled program
    (power-of-two batch) instead of recompiling per occupancy.  Filler
    lanes are cheap: with a padded program their ``n_active=0`` masks
    zero every source, so the Krylov loops converge instantly.
    """
    states = list(states)
    if not states:
        raise ValueError("cannot stack an empty session list")
    if pad_to is not None:
        if pad_to < len(states):
            raise ValueError(
                f"pad_to={pad_to} below cohort size {len(states)}")
        filler = jax.tree.map(jnp.zeros_like, states[0])
        states = states + [filler] * (pad_to - len(states))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def unstack_states(stacked: PisoState, n: int | None = None):
    """Split a cohort-stacked ``PisoState`` back into per-session states.

    Inverse of :func:`stack_states`; ``n`` defaults to the leading axis
    size, and may be smaller when the stack carries trailing filler
    lanes (``stack_states(..., pad_to=...)``) — those are dropped.
    Slicing is exact (no recomputation), so a stack/step/unstack round
    trip equals stepping each session alone up to the batched reduction
    order.
    """
    lead = jax.tree.leaves(stacked)[0].shape[0]
    n = lead if n is None else n
    if n > lead:
        raise ValueError(f"requested {n} sessions from a stack of {lead}")
    return [jax.tree.map(lambda a: a[i], stacked) for i in range(n)]


@dataclasses.dataclass
class SegregatedSolver:
    """Bind a mesh + flow case + repartitioning ratio alpha into a compiled
    segregated stepper.

    The solver is a binder: plans + SolverOps + a registered StepProgram
    (``program_name`` → ``fvm/step_program.PROGRAMS``).  The fused stepper
    **donates** the input ``PisoState`` buffers (keep using the returned
    state, never the argument) and traces ``dt`` as an ordinary operand,
    so varying the timestep size never recompiles.  ``case`` names a
    :class:`~repro.fvm.cases.FlowCase` BC set (or passes one directly);
    the default cavity keeps the seed's exact lid-driven numerics.

    Use the registered specializations — :class:`PisoSolver` (transient)
    and :class:`SimpleSolver` (steady, ``run_steady``) — or
    :func:`make_solver`.
    """

    mesh: CavityMesh
    alpha: int = 1
    nu: float = 0.01
    lid_speed: float = 1.0
    n_correctors: int = 2
    program_name: str = "piso"
    case: str | FlowCase = "cavity"
    # SIMPLE's under-relaxation factors + outer-loop convergence gates
    # (traced per-session operands via the program's extra_keys — unused
    # by transient programs)
    relax_u: float = 0.7
    relax_p: float = 0.3
    tol_continuity: float = 1e-5
    tol_u: float = 1e-6
    max_outer: int = 200
    mom_tol: float = 1e-7
    p_tol: float = 1e-8
    # Krylov iteration caps (the silent-divergence knob: a capped exit now
    # raises StepStats.hit_cap instead of masquerading as convergence).
    # None sizes the pressure cap to the mesh: Jacobi-CG needs about 10.5
    # iterations per cell of the longest edge (361-420 at n = 40, 733-856
    # at n = 80, 1951-2265 at the 210³ cavity), so the default allows 20
    # per cell and never fewer than 2000.
    mom_maxiter: int = 500
    p_maxiter: int | None = None
    update_schedule: str = "device_direct"  # or "host_buffer" (paper fig. 9)
    dtype: jnp.dtype = jnp.float64
    # SPMD solve-phase layout (paper-faithful vs beyond-paper, DESIGN.md §3):
    # solve_mode="stacked" (paper-faithful) replicates solver rows over the
    # assemble axis (C_i "inactive"); solve_mode="full_mesh" row-shards the
    # fused system over the assemble axis too — every chip works during the
    # solve (the paper's oversubscription fix, SPMD-rendered).  When
    # full_mesh is requested without an explicit spmd_mesh, the
    # (solve, assemble) mesh is built from the visible devices via
    # core/comm.make_cfd_mesh and rebuilt on every rebind_alpha (the mesh
    # shape (n_coarse, alpha) follows alpha; the device count n_parts
    # does not).
    solve_mode: str = "stacked"
    spmd_mesh: object | None = None
    full_mesh_solve: bool = False  # legacy alias for solve_mode="full_mesh"
    # Krylov per-iteration backend (repro.solvers.ops): "reference" is the
    # seed's jnp op sequence; "fused" routes each iteration through the
    # krylov_fused Pallas pair (one-pass SpMV+p.Ap, one-pass axpy-pair+
    # Jacobi+dots); "auto" picks per part size and platform — on TPU,
    # fused once a part fills a kernel row block (FUSED_MIN_ROWS),
    # reference below (dispatch overhead beats the saved HBM passes);
    # off-TPU always reference (the kernels would run via the Pallas
    # interpreter inside the solve loop — explicit "fused" forces that
    # for parity tests and benchmarks)
    solver_backend: str = "auto"
    # mixed-precision Krylov policy (repro.solvers.precision): "f64" is
    # the exact pre-policy solve; "f32_ir"/"bf16_ir" run the inner Krylov
    # sweeps at the low storage dtype with an outer f64 iterative-
    # refinement loop, so the converged answer still meets the <=1e-10
    # parity gate while the hot loop streams 2-4x fewer bytes.  Stacked
    # layouts only (the full-mesh shard_map backend stays f64).
    precision: str = "f64"
    # optional shared PlanCache (repro.core.controller) — plans and compiled
    # steppers are then reused when alpha is rebound to a previously seen
    # value, and the instrumented executor's value updates route through
    # the cache's shared compiled-update pool
    plan_cache: object | None = None
    # software-pipelined stepping (fvm/step_program.PipelinedExecutor):
    # "auto" takes the pipelined path whenever the registered program
    # declares one (PISO does; steady programs degrade to the serial
    # executors), "on" demands it (ValueError on a program without a
    # PipelineForm), "off" forces the serial fused path.  The resolved
    # boolean is ``self.pipelined`` and keys the executor memoization.
    pipeline: str = "auto"

    def __post_init__(self):
        if self.p_maxiter is None:
            edge = max(self.mesh.nx, self.mesh.ny, self.mesh.nz)
            self.p_maxiter = max(2000, 20 * edge)
        if self.mesh.n_parts % self.alpha != 0:
            raise ValueError("alpha must divide the number of fine parts")
        if self.full_mesh_solve and self.solve_mode == "stacked":
            self.solve_mode = "full_mesh"
        if self.solve_mode not in ("stacked", "full_mesh"):
            raise ValueError(f"unknown solve_mode {self.solve_mode!r}")
        if self.solver_backend not in ("auto", "fused", "reference"):
            raise ValueError(
                f"unknown solver_backend {self.solver_backend!r}")
        get_policy(self.precision)  # raises on an unknown policy name
        if self.precision != "f64" and self.solve_mode == "full_mesh":
            raise ValueError(
                "mixed-precision policies require solve_mode='stacked' "
                "(the full-mesh shard_map backend is f64-only)")
        if self.pipeline not in ("auto", "on", "off"):
            raise ValueError(f"unknown pipeline mode {self.pipeline!r} "
                             f"(choose auto|on|off)")
        spec = get_program(self.program_name)
        if self.pipeline == "on" and not spec.pipelined:
            raise ValueError(
                f"program {self.program_name!r} declares no pipelined form "
                f"(steady programs cannot software-pipeline across an "
                f"unknown outer trip count) — use pipeline='auto' or 'off'")
        self.pipelined = (self.pipeline == "on"
                          or (self.pipeline == "auto" and spec.pipelined))
        self.full_mesh_solve = self.solve_mode == "full_mesh"
        # size-class serving: a PaddedCavityMesh carries ghost slabs whose
        # activity is decided by a *traced* per-session n_active operand
        # (assembly.dynamic_masks), so one compiled program serves every
        # real slab count of the class — the step functions thread the
        # operand through automatically (see _extras)
        self.padded = getattr(self.mesh, "n_parts_real", None) is not None
        self.n_active = self.mesh.n_parts_active
        if self.padded and self.solve_mode == "full_mesh":
            raise ValueError(
                "padded (size-class) meshes require solve_mode='stacked'")
        # an explicitly supplied mesh is honoured; otherwise full_mesh mode
        # owns (and re-shapes) its mesh across rebind_alpha
        self._auto_mesh = self.spmd_mesh is None
        # bind the flow case: the default cavity goes through the
        # assembly's historical case=None path so lid_speed keeps its
        # exact legacy meaning (bitwise-identical numerics)
        self.case_spec = get_case(self.case)
        self.case = self.case_spec.name
        asm_case = None if self.case == "cavity" else self.case_spec
        self.asm = CavityAssembly(self.mesh, nu=self.nu,
                                  lid_speed=self.lid_speed, dtype=self.dtype,
                                  case=asm_case)
        # identity repartition for the momentum (fine-partition) matrix
        self.plan_mom: RepartitionPlan = self._plan_for(1)
        self._update = (update_device_direct
                        if self.update_schedule == "device_direct"
                        else update_host_buffer)
        # compiled program executors per (alpha, solve_mode, solver_backend):
        # revisiting a layout (adaptive controller oscillating between
        # neighbours, or a mode/backend A/B) reuses trace + XLA work
        self._programs: dict[tuple, ProgramExecutors] = {}
        self.rebind_alpha(self.alpha)

    def _plan_for(self, alpha: int) -> RepartitionPlan:
        if self.plan_cache is not None:
            # same key convention as RepartitionController.plan(): the solve
            # mode is its own cache-key component, so stacked and full-mesh
            # sessions sharing one PlanCache never alias cached artifacts
            return self.plan_cache.plan_for_mesh(self.mesh, alpha, "dia",
                                                 mode=self.solve_mode,
                                                 backend=self.solver_backend,
                                                 precision=self.precision)
        return plan_for_mesh(self.mesh, alpha)

    def rebind_alpha(self, alpha: int) -> None:
        """Swap the pressure-side repartitioning ratio (controller hook).

        The velocity/pressure state is alpha-independent (fine-partition
        layout), so a running simulation can switch plans between steps.
        Plans come from ``plan_cache`` when present; the built StepProgram
        and its executors are memoized per (program, alpha, mode, backend,
        precision, pipelined), so a revisited alpha pays zero re-plan,
        re-trace or re-compile cost.
        """
        if self.mesh.n_parts % alpha != 0:
            raise ValueError("alpha must divide the number of fine parts")
        self.alpha = alpha
        self.plan_p: RepartitionPlan = self._plan_for(alpha)
        self.n_coarse = self.mesh.n_parts // alpha
        if self.solve_mode == "full_mesh":
            from repro.core.comm import make_cfd_mesh

            if self._auto_mesh:
                self.spmd_mesh = make_cfd_mesh(self.n_coarse, alpha)
            elif tuple(self.spmd_mesh.devices.shape) != (self.n_coarse,
                                                         alpha):
                # an explicitly supplied mesh no longer fits the new alpha:
                # reshape it over the same devices (the shard_map SpMV
                # splits by the mesh axis sizes — a stale shape would crash
                # or, worse, silently mis-slice)
                self.spmd_mesh = make_cfd_mesh(
                    self.n_coarse, alpha,
                    devices=list(self.spmd_mesh.devices.flat))
        key = (self.program_name, alpha, self.solve_mode,
               self.solver_backend, self.precision, self.pipelined)
        exe = self._programs.get(key)
        if exe is None:
            # a fresh program binds fresh closures over the new plans, so
            # jax.jit traces per binding (the seed's bound-method stepper
            # aliased one trace across rebinds and kept executing the
            # first alpha's compiled program)
            exe = self._programs[key] = ProgramExecutors(
                get_program(self.program_name).build(self))
        self._exec = exe

    # ---- helpers ------------------------------------------------------
    @property
    def program(self):
        """The bound :class:`~repro.fvm.step_program.StepProgram`."""
        return self._exec.program

    def _extra_value(self, key: str, filler: bool = False):
        """One extra traced operand by name (``program.extra_keys``).

        ``filler=True`` is the value a zero lane of a padded cohort
        carries (``n_active=0`` deactivates every mask; the relaxation
        factors keep their real values — harmless on a zeroed state)."""
        if key == "n_active":
            return jnp.asarray(0 if filler else self.n_active, jnp.int32)
        if key == "relax_u":
            return jnp.asarray(self.relax_u, self.dtype)
        if key == "relax_p":
            return jnp.asarray(self.relax_p, self.dtype)
        raise KeyError(f"program asks for unknown extra operand {key!r}")

    def _extras(self) -> tuple:
        """Extra traced operands the bound program expects per step.

        Driven by ``program.extra_keys``: a padded (size-class) program
        takes the real slab count ``n_active``; SIMPLE adds its
        under-relaxation factors.  Exposed so the serving engine can
        build the *stacked* per-lane vectors for a batched cohort
        dispatch."""
        return tuple(self._extra_value(k) for k in self.program.extra_keys)

    def _filler_extras(self) -> tuple:
        """The extras a padded cohort's zero filler lane carries."""
        return tuple(self._extra_value(k, filler=True)
                     for k in self.program.extra_keys)

    def initial_state(self) -> PisoState:
        P, m, F = self.mesh.n_parts, self.mesh.n_cells, self.mesh.n_faces
        B = self.mesh.plane
        U = jnp.zeros((P, m, 3), self.dtype)
        return PisoState(
            U=U,
            p=jnp.zeros((P, m), self.dtype),
            phi=jnp.zeros((P, F), self.dtype),
            phi_if=jnp.zeros((P, 2, B), self.dtype),
            # Dirichlet boundary fluxes are fixed from step 0 (exact zeros
            # for the cavity; the inlet flux for inlet/outlet cases)
            phi_b=self.asm.boundary_flux(U),
        )

    def _solve_constraint(self, x):
        """Pin the solve-phase layout when running under an SPMD mesh."""
        from repro.core.comm import solve_constraint

        return solve_constraint(self.spmd_mesh, x,
                                full_mesh=self.full_mesh_solve)

    def _use_full_mesh(self, plan: RepartitionPlan) -> bool:
        """Full-mesh SpMV applies to multi-part fused systems only: the
        momentum (alpha=1, fine-partition) solve keeps the stacked path."""
        return (self.solve_mode == "full_mesh" and self.spmd_mesh is not None
                and plan.alpha > 1)

    def _bands(self, plan: RepartitionPlan, diag, upper, lower, iface):
        """LDU buffers → repartitioned DIA bands via the update pattern."""
        buffers = buffer_from_parts(diag, upper, lower, iface)  # (P_f, L)
        n_c = buffers.shape[0] // plan.alpha
        grouped = buffers.reshape(n_c, plan.alpha, plan.buffer_len)
        return self._update(plan, grouped, target="dia")

    def backend_for(self, plan: RepartitionPlan) -> str:
        """The concrete SolverOps backend of ``plan``'s solve: resolved
        per part size (``m_coarse`` rows stacked, ``m_coarse / alpha``
        per full-mesh shard), platform and precision policy."""
        rows = (plan.m_coarse // plan.alpha if self._use_full_mesh(plan)
                else plan.m_coarse)
        return resolve_backend(self.solver_backend, rows,
                               precision=self.precision)

    def describe_backends(self) -> str:
        """One line naming every resolved solve backend and the policy."""
        return (f"solver backends: momentum="
                f"{self.backend_for(self.plan_mom)} pressure="
                f"{self.backend_for(self.plan_p)} (requested "
                f"{self.solver_backend}, {jax.default_backend()}); "
                f"precision={self.precision}")

    def _solver_ops(self, plan: RepartitionPlan, bands, diag):
        """Bind the (bands, diag) system into a SolverOps bundle.

        Dispatches on layout (stacked vs full-mesh) x backend (reference
        vs fused, resolved per part size — ``plan.m_coarse`` rows stacked,
        ``m_coarse / alpha`` per full-mesh shard).  ``diag`` is the fused
        system's diagonal in the stacked layout; full-mesh paths constrain
        it to the (solve, assemble) row sharding here.
        """
        offsets = tuple(int(o) for o in plan.dia_offsets)
        if self._use_full_mesh(plan):
            # beyond-paper mode: explicit shard_map SpMV with linear halo
            # permutes — rows sharded over BOTH mesh axes (GSPMD alone
            # re-gathers banded shifts; see EXPERIMENTS.md §Perf C3)
            from repro.sparse.shardmap_spmv import (make_fused_ops_full_mesh,
                                                    make_jacobi_full_mesh,
                                                    make_spmv_full_mesh)

            backend = self.backend_for(plan)
            diag_c = self._solve_constraint(diag)
            kw = dict(offsets=offsets, plane=plan.plane,
                      n_coarse=self.n_coarse, alpha=plan.alpha,
                      m_coarse=plan.m_coarse)
            if backend == "fused":
                return make_fused_ops_full_mesh(self.spmd_mesh, bands,
                                                diag_c, **kw)
            fm = make_spmv_full_mesh(self.spmd_mesh, **kw)
            return reference_ops(
                lambda x: fm(bands, x),
                make_jacobi_full_mesh(self.spmd_mesh, diag_c))

        backend = self.backend_for(plan)
        policy = get_policy(self.precision)
        if backend == "fused":
            return fused_stacked_ops(bands, diag, offsets=offsets,
                                     plane=plan.plane, policy=policy)

        return stacked_reference_ops(bands, diag, offsets=offsets,
                                     plane=plan.plane, policy=policy)

    # ---- the executors ---------------------------------------------------
    @property
    def _stepper(self):
        """The advancing executor of this binding: the software-pipelined
        one when the resolved ``pipeline`` knob says so (identical
        external contract — traced dt, donated state, one dispatch per
        rolled window), the serial fused one otherwise."""
        return (self._exec.pipelined if self.pipelined
                else self._exec.fused)

    def step(self, state: PisoState, dt: float):
        """One timestep as ONE fused XLA dispatch.

        ``dt`` is traced (no recompile across timestep sizes) and
        ``state`` is DONATED — its buffers are invalidated by the call;
        keep using the returned state.  Returns ``(state, StepStats)``.
        """
        return self._stepper.step(state, dt, *self._extras())

    def run_steps(self, state: PisoState, dt: float, n_steps: int):
        """Advance ``n_steps`` timesteps as ONE scan-rolled XLA dispatch.

        Returns ``(state, stats)`` where every ``StepStats`` leaf carries
        a leading ``n_steps`` axis (per-step history of the window).
        ``state`` is donated; each distinct window length compiles once.
        """
        return self._stepper.run_steps(state, dt, n_steps,
                                       *self._extras())

    def lower_steps(self, state: PisoState, dt: float, n_steps: int):
        """The compiled window that :meth:`run_steps` dispatches for
        ``n_steps`` (its HLO names each phase in ``op_name``).  ``state``
        may hold ``jax.ShapeDtypeStruct`` leaves."""
        return self._stepper.lower_steps(state, dt, n_steps,
                                         *self._extras())

    def batched_executor(self, batch: int):
        """The cohort stepper for ``batch`` stacked sessions.

        ``jax.vmap`` of this binding's program over a leading session
        axis (:class:`~repro.fvm.step_program.BatchedExecutor`, or its
        pipelined variant when the resolved ``pipeline`` knob is on),
        memoized per cohort size alongside the other executors of the
        current ``(alpha, solve_mode, solver_backend, pipelined)``
        binding.  Any solver with an equal binding on the same mesh
        produces a numerically interchangeable batched program — what
        lets the serving engine step a whole cohort through one member's
        executor.
        """
        if self.pipelined:
            return self._exec.batched_pipelined(batch)
        return self._exec.batched(batch)

    def timed_step(self, state: PisoState, dt: float):
        """One PISO step with per-phase wall timers (controller feedback).

        Phase attribution follows the paper's two partitions: **assembly**
        is the whole fine-partition share (momentum predictor including its
        BiCGStab solve, pressure assembly, flux/velocity corrections);
        **update** is the repartitioning coefficient update into the coarse
        plan; **solve** the coarse-partition pressure CG; **halo** the
        estimated per-iteration neighbour exchange inside that solve (the
        program's probe hook: one probed exchange x iteration count — the
        exchange cannot be timed from inside the jitted CG loop).

        Numerically identical to :meth:`step` (the same StepProgram phases,
        jitted per phase rather than fused); the first call after
        construction or :meth:`rebind_alpha` to a new alpha includes
        trace+compile time, so controllers should discard warm-up samples
        (``ControllerConfig.warmup``).  Does NOT donate ``state``.
        Returns ``(state, stats, PhaseBreakdown)``.
        """
        return self._exec.instrumented.timed_step(state, dt,
                                                  *self._extras())

    def run(self, n_steps: int, dt: float, state: PisoState | None = None,
            scan_steps: int | None = None):
        """Run a window via the scan-rolled executor.

        Returns ``(state, stats)`` with per-step stacked ``StepStats``
        (leading axis ``n_steps``) — the full convergence history of the
        run, not just its last step.  By default the whole run is ONE
        XLA dispatch; ``scan_steps`` caps the rolled window length
        (ceil(n_steps/scan_steps) dispatches, stats concatenated), which
        bounds the compile cache when callers vary ``n_steps`` — the
        serving engine and launcher cap their windows the same way.
        """
        from repro.fvm.step_program import roll_schedule

        state = self.initial_state() if state is None else state
        if scan_steps is None:
            return self.run_steps(state, dt, n_steps)
        windows = []
        for _sample, chunk in roll_schedule(0, n_steps, None,
                                            cap=scan_steps):
            state, w = self.run_steps(state, dt, chunk)
            windows.append(w)
        stats = jax.tree.map(lambda *xs: jnp.concatenate(xs), *windows)
        return state, stats

    def run_steady(self, dt: float = 1.0, state: PisoState | None = None,
                   max_outer: int | None = None):
        """Outer-iterate to the program's convergence predicate as ONE
        ``lax.while_loop`` dispatch (steady-state programs only — the
        program must declare ``converged``).

        ``dt`` is ignored by a true steady program (SIMPLE assembles with
        an infinite timestep) but stays a traced operand so the executor
        signature is uniform.  Returns ``(state, stats, n_outer)`` with
        ``stats`` the last outer iteration's residuals and ``n_outer``
        the iteration count actually run (== the cap when unconverged).
        Donates ``state``.
        """
        state = self.initial_state() if state is None else state
        cap = self.max_outer if max_outer is None else max_outer
        return self._exec.fused.run_converged(state, dt, cap,
                                              *self._extras())


@dataclasses.dataclass
class PisoSolver(SegregatedSolver):
    """The transient PISO marcher (the paper's measured solver)."""

    program_name: str = "piso"


@dataclasses.dataclass
class SimpleSolver(SegregatedSolver):
    """The steady-state under-relaxed SIMPLE iterator (``run_steady``).

    One pressure correction per outer iteration (simpleFoam), implicit
    momentum under-relaxation by ``relax_u``, explicit pressure
    relaxation by ``relax_p``; converged when both the continuity error
    and the outer velocity change drop below their gates.
    """

    program_name: str = "simple"
    n_correctors: int = 1


SOLVERS: dict[str, type] = {"piso": PisoSolver, "simple": SimpleSolver}


def make_solver(program: str, mesh: CavityMesh, **kw) -> SegregatedSolver:
    """Construct the registered solver specialization for a program name."""
    try:
        cls = SOLVERS[program]
    except KeyError:
        raise KeyError(f"unknown program {program!r} "
                       f"(registered: {tuple(sorted(SOLVERS))})") from None
    return cls(mesh, **kw)


def _offdiag3(asm: CavityAssembly, sysM, U: jax.Array) -> jax.Array:
    """Off-diagonal apply per velocity component: (P, m, 3)."""
    return jnp.stack([asm.offdiag_apply(sysM, U[..., c]) for c in range(3)],
                     axis=2)
