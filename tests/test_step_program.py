"""StepProgram: one declarative PISO phase graph, compiled three ways.

Covers the executor-equivalence acceptance bar (fused per-step vs
scan-rolled vs instrumented: bitwise-close states, identical Krylov
iteration counts, across solver backends), the dt-retrace regression, the
PisoState donation contract, and the program-validation errors.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.cost_model import PhaseBreakdown
from repro.fvm.mesh import CavityMesh
from repro.fvm.piso import PisoSolver, stack_states, unstack_states
from repro.fvm.step_program import Phase, StepProgram

from hyp_compat import given, settings, st

DT = 1e-3


def fresh(solver):
    return solver.initial_state()


# ---------------------------------------------------------------------------
# executor equivalence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["reference", "fused"])
def test_executors_equivalent_per_backend(backend):
    """Per-step fused vs scan-rolled vs instrumented: states match to
    <= 1e-10 with IDENTICAL CG/BiCGStab iteration counts (same program,
    three compilations) — on both SolverOps backends."""
    n_steps = 3
    mesh = CavityMesh.cube(4, 2)
    mk = lambda: PisoSolver(mesh, alpha=2, solver_backend=backend)

    s_step = mk()
    st_step = fresh(s_step)
    per_step = []
    for _ in range(n_steps):
        st_step, stats = s_step.step(st_step, DT)
        per_step.append(stats)

    s_roll = mk()
    st_roll, rolled = s_roll.run_steps(fresh(s_roll), DT, n_steps)

    s_inst = mk()
    st_inst = fresh(s_inst)
    for _ in range(n_steps):
        st_inst, stats_inst, sample = s_inst.timed_step(st_inst, DT)

    for a, b in ((st_roll, st_step), (st_inst, st_step)):
        np.testing.assert_allclose(np.asarray(a.U), np.asarray(b.U),
                                   atol=1e-10)
        np.testing.assert_allclose(np.asarray(a.p), np.asarray(b.p),
                                   atol=1e-10)
    # identical solver iteration counts, step by step
    assert rolled.p_iters.shape == (n_steps, 2)
    assert rolled.p_iters.tolist() == [
        [int(i) for i in s.p_iters] for s in per_step]
    assert rolled.mom_iters.tolist() == [int(s.mom_iters) for s in per_step]
    assert [int(i) for i in stats_inst.p_iters] == \
        [int(i) for i in per_step[-1].p_iters]
    # the instrumented walk produced a well-formed breakdown
    assert isinstance(sample, PhaseBreakdown)
    assert sample.total > 0.0
    assert min(sample.assembly, sample.update, sample.halo, sample.solve) >= 0


def test_rolled_window_is_one_dispatch():
    """An 8-step window through run_steps is ONE host→XLA dispatch; the
    per-step path pays eight.  ``_stepper`` is the routed executor —
    pipelined under PISO's default pipeline="auto", fused under "off" —
    and the contract holds on both."""
    mesh = CavityMesh.cube(4, 2)
    for mode in ("auto", "off"):
        s = PisoSolver(mesh, alpha=2, pipeline=mode)
        base = s._stepper.dispatches
        s.run_steps(fresh(s), DT, 8)
        assert s._stepper.dispatches - base == 1

        st = fresh(s)
        base = s._stepper.dispatches
        for _ in range(8):
            st, _ = s.step(st, DT)
        assert s._stepper.dispatches - base == 8


# ---------------------------------------------------------------------------
# dt tracing + donation
# ---------------------------------------------------------------------------

def test_dt_is_traced_not_static():
    """Regression: the seed jitted the step with static_argnames=("dt",),
    recompiling per distinct timestep size.  dt is now a traced operand —
    two dt values share one compilation-cache entry."""
    # the routed stepper (pipelined under the default "auto") and the
    # explicit serial fused path both keep dt traced
    for mode in ("auto", "off"):
        s = PisoSolver(CavityMesh.cube(4, 2), alpha=2, pipeline=mode)
        st, _ = s.step(fresh(s), 1e-3)
        st, _ = s.step(st, 2e-3)     # different dt: must NOT retrace
        st, _ = s.step(st, 5e-4)
        tc = s._stepper.trace_count
        # strict: the -1 "cache hidden" sentinel must FAIL here, not pass
        # vacuously — if jax drops _cache_size(), replace this meter, don't
        # let the dt-retrace regression go unwatched
        assert tc == 1, f"dt changed -> {tc} compilations (expected 1)"
        # and the rolled executor shares the behaviour
        s.run_steps(st, 1e-3, 2)
        st2, _ = s.run_steps(fresh(s), 2e-3, 2)
        assert len(s._stepper._rolled) == 1


def test_state_donation_invalidate_and_alias():
    """The fused step donates the PisoState buffers: the input is
    invalidated after the call, and the compiled HLO aliases all four
    state inputs to outputs (no defensive copy of the flow state)."""
    s = PisoSolver(CavityMesh.cube(4, 2), alpha=2)
    st = fresh(s)
    out, _ = s.step(st, DT)
    assert st.U.is_deleted() and st.p.is_deleted()
    assert not out.U.is_deleted()

    hlo = s._exec.fused.lower_step(fresh(s), DT).as_text()
    header = hlo.splitlines()[0]
    assert "input_output_alias" in header, header
    # all four PisoState leaves of argument 0 are aliased in place
    assert header.count("may-alias") + header.count("must-alias") >= 4, header


def test_timed_step_does_not_donate():
    s = PisoSolver(CavityMesh.cube(4, 2), alpha=2)
    st = fresh(s)
    s.timed_step(st, DT)
    assert not st.U.is_deleted()


# ---------------------------------------------------------------------------
# program validation
# ---------------------------------------------------------------------------

def _mini_program(phases):
    return StepProgram(phases=tuple(phases),
                       seed=lambda state, dt: {"x": state, "dt": dt},
                       finalize=lambda env: (env["x"], None),
                       seed_keys=("x", "dt"))


def test_program_validates_dataflow():
    ok = Phase("double", "solve", ("x",), ("x",), lambda x: 2 * x)
    _mini_program([ok])  # fine
    with pytest.raises(ValueError, match="neither seeded nor produced"):
        _mini_program([Phase("bad", "solve", ("y",), ("x",), lambda y: y)])
    with pytest.raises(ValueError, match="unknown tag"):
        _mini_program([Phase("bad", "gpu", ("x",), ("x",), lambda x: x)])
    with pytest.raises(ValueError, match="probe_iters"):
        _mini_program([Phase("bad", "solve", ("x",), ("x",), lambda x: x,
                             probe=lambda x: x, probe_inputs=("x",),
                             probe_iters="iters")])


def test_program_output_arity_checked():
    bad = Phase("pair", "solve", ("x",), ("a", "b", "c"),
                lambda x: (x, x))  # 2 values for 3 outputs
    prog = _mini_program([bad])
    with pytest.raises(ValueError, match="returned 2 values"):
        prog.as_step_fn()(jnp.ones(3), 0.1)


# ---------------------------------------------------------------------------
# plan-cache integration (pooled updates ride the instrumented executor)
# ---------------------------------------------------------------------------

def test_instrumented_uses_pooled_updates_with_plan_cache():
    from repro.core.controller import PlanCache

    cache = PlanCache()
    mesh = CavityMesh.cube(4, 2)
    s = PisoSolver(mesh, alpha=2, plan_cache=cache)
    ups = [ph for ph in s.program.phases if ph.name in ("update_mom",
                                                        "update_p")]
    assert ups and all(ph.instrumented_fn is not None for ph in ups)
    # pooled path is numerically the plain path
    s_plain = PisoSolver(mesh, alpha=2)
    st_a, _, _ = s.timed_step(fresh(s), DT)
    st_b, _, _ = s_plain.timed_step(fresh(s_plain), DT)
    np.testing.assert_allclose(np.asarray(st_a.U), np.asarray(st_b.U),
                               atol=1e-12)
    assert cache.pool.misses >= 1  # the updates really went through the pool


def test_roll_schedule_cadence():
    from repro.fvm.step_program import roll_schedule

    # anchored grid: steps 0,3,6 sample; stretches run to the next sample
    assert list(roll_schedule(0, 7, 3)) == [
        (True, 1), (False, 2), (True, 1), (False, 2), (True, 1)]
    # resuming mid-grid keeps the anchor (engine across step_session calls)
    assert list(roll_schedule(7, 3, 3)) == [(False, 2), (True, 1)]
    # cap bounds each rolled dispatch (compile-cache growth bound)
    assert list(roll_schedule(1, 10, 100, cap=4)) == [
        (False, 4), (False, 4), (False, 2)]
    # every=None never samples (non-adaptive sessions)
    assert list(roll_schedule(0, 5, None)) == [(False, 5)]
    assert list(roll_schedule(0, 5, None, cap=2)) == [
        (False, 2), (False, 2), (False, 1)]
    with pytest.raises(ValueError):
        list(roll_schedule(0, 5, 0))


@settings(max_examples=200, deadline=None)
@given(start=st.integers(min_value=0, max_value=60),
       n_steps=st.integers(min_value=1, max_value=40),
       every=st.one_of(st.none(), st.integers(min_value=1, max_value=9)),
       cap=st.one_of(st.none(), st.integers(min_value=1, max_value=7)))
def test_roll_schedule_properties(start, n_steps, every, cap):
    """Invariants of the engine cadence for any (start, n_steps, every,
    cap): chunks cover exactly n_steps, samples land exactly on the
    absolute grid, the cap bounds every rolled stretch, and every=None is
    pure rolled stretches."""
    from repro.fvm.step_program import roll_schedule

    stretches = list(roll_schedule(start, n_steps, every, cap=cap))
    # full cover, in order, no empty stretches
    assert sum(c for _, c in stretches) == n_steps
    assert all(c >= 1 for _, c in stretches)

    # replay the schedule against the absolute step grid
    pos = start
    for is_sample, chunk in stretches:
        if is_sample:
            assert every is not None and chunk == 1
            assert pos % every == 0, (pos, every)
        else:
            if cap is not None:
                assert chunk <= cap
            if every is not None:
                # a rolled stretch never crosses (or touches) a sample
                # point except at its start boundary
                assert all((pos + k) % every != 0 for k in range(chunk)), \
                    (pos, chunk, every)
        pos += chunk
    assert pos == start + n_steps

    if every is None:
        assert not any(s for s, _ in stretches)
        if cap is None:
            # a single uncapped stretch
            assert stretches == [(False, n_steps)]
        else:
            # ceil(n/cap) capped stretches, all but the last full
            assert len(stretches) == -(-n_steps // cap)
            assert all(c == cap for _, c in stretches[:-1])
    else:
        # every sample point inside [start, start+n_steps) is sampled
        n_samples = sum(1 for k in range(n_steps)
                        if (start + k) % every == 0)
        assert sum(1 for s, _ in stretches if s) == n_samples


def test_run_scan_steps_cap_concatenates_windows():
    """run(scan_steps=k) chunks the roll into capped windows (bounded
    compile cache) and concatenates the per-step stats — numerically the
    single-window default."""
    mesh = CavityMesh.cube(4, 2)
    a = PisoSolver(mesh, alpha=2)
    st_a, stats_a = a.run(5, DT)
    b = PisoSolver(mesh, alpha=2)
    st_b, stats_b = b.run(5, DT, scan_steps=2)
    np.testing.assert_allclose(np.asarray(st_b.U), np.asarray(st_a.U),
                               atol=1e-10)
    assert stats_b.p_iters.shape == (5, 2)
    assert stats_b.p_iters.tolist() == stats_a.p_iters.tolist()
    assert sorted(b._stepper._rolled) == [1, 2]  # windows 2+2+1


# ---------------------------------------------------------------------------
# the batched (cohort) executor
# ---------------------------------------------------------------------------

def test_stack_unstack_round_trip():
    s = PisoSolver(CavityMesh.cube(4, 2), alpha=2)
    states = [s.initial_state() for _ in range(3)]
    stacked = stack_states(states)
    assert stacked.U.shape == (3,) + states[0].U.shape
    back = unstack_states(stacked)
    assert len(back) == 3
    for a, b in zip(back, states):
        np.testing.assert_array_equal(np.asarray(a.U), np.asarray(b.U))
    with pytest.raises(ValueError):
        stack_states([])
    # n below the lead axis drops trailing lanes (the lane-class filler
    # contract); asking for more sessions than lanes is still an error
    assert len(unstack_states(stacked, 2)) == 2
    with pytest.raises(ValueError):
        unstack_states(stacked, 4)
    padded = stack_states(states, pad_to=4)
    assert padded.U.shape == (4,) + states[0].U.shape
    assert float(np.abs(np.asarray(padded.U[3])).max()) == 0.0
    with pytest.raises(ValueError):
        stack_states(states, pad_to=2)


def test_batched_executor_matches_solo_runs():
    """A 3-session cohort through the batched scan-rolled executor matches
    each session's solo run (<= 1e-10, identical per-step Krylov iteration
    counts) with ONE dispatch for the whole cohort window."""
    mesh = CavityMesh.cube(4, 2)
    solver = PisoSolver(mesh, alpha=2)
    dts = [1e-3, 2e-3, 5e-4]
    n_steps = 4

    exe = solver.batched_executor(3)
    states = stack_states([solver.initial_state() for _ in dts])
    out, stats = exe.run_steps(states, jnp.asarray(dts, solver.dtype),
                               n_steps)
    assert exe.dispatches == 1
    assert stats.p_iters.shape == (n_steps, 3, 2)

    for i, dt in enumerate(dts):
        solo = PisoSolver(mesh, alpha=2)
        st, w = solo.run_steps(solo.initial_state(), dt, n_steps)
        got = jax.tree.map(lambda a, i=i: a[i], out)
        np.testing.assert_allclose(np.asarray(got.U), np.asarray(st.U),
                                   atol=1e-10)
        np.testing.assert_allclose(np.asarray(got.p), np.asarray(st.p),
                                   atol=1e-10)
        assert stats.p_iters[:, i].tolist() == w.p_iters.tolist()
        assert stats.mom_iters[:, i].tolist() == w.mom_iters.tolist()


def test_batched_executor_donates_and_checks_shapes():
    solver = PisoSolver(CavityMesh.cube(4, 2), alpha=2)
    exe = solver.batched_executor(2)
    states = stack_states([solver.initial_state(), solver.initial_state()])
    dts = jnp.asarray([1e-3, 2e-3], solver.dtype)
    out, _ = exe.step(states, dts)
    assert states.U.is_deleted() and not out.U.is_deleted()
    # cohort-shape mismatches fail loudly, before tracing
    with pytest.raises(ValueError, match="cohort shape"):
        exe.step(out, jnp.asarray([1e-3], solver.dtype))
    three = stack_states([solver.initial_state() for _ in range(3)])
    with pytest.raises(ValueError, match="cohort shape"):
        exe.step(three, jnp.asarray([1e-3] * 3, solver.dtype))
    with pytest.raises(ValueError):
        solver.batched_executor(0)


def test_batched_timed_step_apportions_rows():
    """The batched instrumented walk returns one PhaseBreakdown row per
    session: apportioned phase walls (cohort wall / S), per-session halo
    share from each session's own CG iteration count, stacked StepStats."""
    solver = PisoSolver(CavityMesh.cube(4, 2), alpha=2)
    exe = solver.batched_executor(2)
    states = stack_states([solver.initial_state(), solver.initial_state()])
    dts = jnp.asarray([1e-3, 2e-3], solver.dtype)
    out, stats, rows = exe.timed_step(states, dts)
    assert exe.samples == 1
    assert len(rows) == 2
    for row in rows:
        assert isinstance(row, PhaseBreakdown)
        assert row.total > 0.0
        assert min(row.assembly, row.update, row.halo, row.solve) >= 0
    assert stats.p_iters.shape == (2, 2)   # (S, n_correctors)
    assert not states.U.is_deleted()       # instrumented path: no donation
    # numerics match the solo instrumented walk
    solo = PisoSolver(CavityMesh.cube(4, 2), alpha=2)
    st, _, _ = solo.timed_step(solo.initial_state(), 1e-3)
    np.testing.assert_allclose(np.asarray(out.U[0]), np.asarray(st.U),
                               atol=1e-10)


# ---------------------------------------------------------------------------
# the software-pipelined executor (PipelineForm)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["reference", "fused"])
def test_pipelined_matches_fused_per_backend(backend):
    """pipeline="on" vs pipeline="off" run_steps: states <= 1e-10 apart
    with IDENTICAL per-step Krylov iteration counts on both SolverOps
    backends — the overlap schedule reorders work, it must not change it."""
    n_steps = 3
    mesh = CavityMesh.cube(4, 2)
    serial = PisoSolver(mesh, alpha=2, solver_backend=backend,
                        pipeline="off")
    piped = PisoSolver(mesh, alpha=2, solver_backend=backend, pipeline="on")
    st_s, w_s = serial.run_steps(fresh(serial), DT, n_steps)
    st_p, w_p = piped.run_steps(fresh(piped), DT, n_steps)
    np.testing.assert_allclose(np.asarray(st_p.U), np.asarray(st_s.U),
                               atol=1e-10)
    np.testing.assert_allclose(np.asarray(st_p.p), np.asarray(st_s.p),
                               atol=1e-10)
    assert w_p.p_iters.tolist() == w_s.p_iters.tolist()
    assert w_p.mom_iters.tolist() == w_s.mom_iters.tolist()
    # the window's health flags agree too (same solves, same verdicts)
    assert w_p.diverged.tolist() == w_s.diverged.tolist()
    assert w_p.hit_cap.tolist() == w_s.hit_cap.tolist()


def test_pipelined_schedule_and_frontier():
    """The dependence scheduler derives the overlap frontier from the
    declared phase dataflow alone: the momentum solve (a blocking phase)
    runs with the next pressure-matrix assembly + coefficient update —
    neither consumes anything the solve produces."""
    from repro.fvm.step_program import PHASE_TAGS

    s = PisoSolver(CavityMesh.cube(4, 2), alpha=2)
    exe = s._exec.pipelined
    names = [ph.name for ph in exe.schedule]
    # every pipeline phase scheduled exactly once
    assert sorted(names) == sorted(
        ph.name for ph in s.program.pipeline.phases)
    # the legal frontier under solve_mom: the matrix-only pressure half
    assert set(exe.frontier["solve_mom"]) == {"assemble_p_mat", "update_p"}
    # frontier phases are scheduled BEFORE the blocking solve they overlap
    for ph in exe.frontier["solve_mom"]:
        assert names.index(ph) < names.index("solve_mom")
    # blocking phases sort after independent work of their level
    assert PHASE_TAGS == PhaseBreakdown.TIME_FIELDS


@pytest.mark.parametrize("pipeline", ["off", "on"])
def test_window_hlo_names_every_phase(pipeline):
    """The compiled window of ``run_steps`` (the fused executor's, and the
    pipelined one's) carries each phase name of the schedule that runs as
    a component of some instruction's ``op_name``, beside the finalize
    scope; it lowers from ``ShapeDtypeStruct`` leaves, as a caller
    holding only a donated state's shapes asks for it.  Two steps: in a
    one-step pipelined window the trailing ``grad_p`` feeds only the
    dropped ring, and the compiler removes it."""
    import re

    s = PisoSolver(CavityMesh.cube(4, 2), alpha=2, pipeline=pipeline)
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          fresh(s))
    exe = s._exec.pipelined if s.pipelined else s._exec.fused
    assert "solve_p" in s.lower_steps(shapes, DT, 1).as_text()
    hlo = exe.lower_steps(shapes, DT, 2).as_text()
    scopes = {part for op in re.findall(r'op_name="([^"]*)"', hlo)
              for part in op.split("/")}
    phases = s.program.pipeline.phases if s.pipelined else s.program.phases
    names = {ph.name for ph in phases}
    assert len(names) == (9 if s.pipelined else 7)
    assert names | {"finalize"} <= scopes, sorted(scopes)


@pytest.mark.parametrize("case", ["cavity", "backstep"])
@pytest.mark.parametrize("pipeline", ["off", "on"])
def test_window_hlo_has_no_scatter(case, pipeline):
    """The assembly's face sums are gathers over static row tables
    (``core.rows.add_rows``): the compiled PISO window, serial and
    pipelined, of a cavity and of an inlet/outlet case holds no scatter
    instruction."""
    import re

    s = PisoSolver(CavityMesh.cube(10, 2), alpha=2, pipeline=pipeline,
                   case=case)
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          fresh(s))
    hlo = s.lower_steps(shapes, DT, 1).as_text()
    assert re.search(r"[ )]gather\(", hlo)
    assert not re.findall(r"[ )]scatter\(", hlo)


def test_pipelined_donates_state_and_aliases_buffers():
    s = PisoSolver(CavityMesh.cube(4, 2), alpha=2, pipeline="on")
    st = fresh(s)
    out, _ = s.step(st, DT)
    assert st.U.is_deleted() and st.p.is_deleted()
    assert not out.U.is_deleted()
    hlo = s._exec.pipelined.lower_step(fresh(s), DT).as_text()
    header = hlo.splitlines()[0]
    assert "input_output_alias" in header, header
    assert header.count("may-alias") + header.count("must-alias") >= 4, header


def test_pipelined_health_flag_parity_under_forced_cap():
    """A misconfigured pressure solve (unreachable tol at a tiny cap)
    must raise the same hit_cap flags through the pipelined window as
    through the serial roll — the supervisor's window_verdict may not
    depend on which executor advanced the session."""
    from repro.serving.supervisor import window_verdict

    mesh = CavityMesh.cube(4, 2)
    windows = {}
    for mode in ("off", "on"):
        s = PisoSolver(mesh, alpha=2, pipeline=mode)
        s.p_tol, s.p_maxiter = 1e-30, 2
        s._programs.clear()
        s.rebind_alpha(s.alpha)
        _, windows[mode] = s.run_steps(fresh(s), DT, 4)
    assert windows["on"].hit_cap.tolist() == windows["off"].hit_cap.tolist()
    assert bool(windows["on"].hit_cap.any())
    assert windows["on"].diverged.tolist() == \
        windows["off"].diverged.tolist()
    assert window_verdict(windows["on"]) == window_verdict(windows["off"])


def test_pipeline_knob_resolution_and_errors():
    """auto resolves per program spec; "on" demands a PipelineForm; the
    resolved flag keys the executor memoization."""
    from repro.fvm.piso import SimpleSolver
    from repro.fvm.step_program import (BatchedPipelinedExecutor,
                                        FusedExecutor, PipelinedExecutor)

    mesh = CavityMesh.cube(4, 2)
    auto = PisoSolver(mesh, alpha=2)
    assert auto.pipelined and isinstance(auto._stepper, PipelinedExecutor)
    off = PisoSolver(mesh, alpha=2, pipeline="off")
    assert not off.pipelined and isinstance(off._stepper, FusedExecutor)
    assert isinstance(auto.batched_executor(2), BatchedPipelinedExecutor)
    # the memo key carries the resolved boolean (and the precision policy)
    assert ("piso", 2, "stacked", "auto", "f64", True) in auto._programs
    assert ("piso", 2, "stacked", "auto", "f64", False) in off._programs

    # steady programs: auto degrades, "on" refuses
    simple = SimpleSolver(mesh, alpha=2)
    assert not simple.pipelined
    assert isinstance(simple._stepper, FusedExecutor)
    with pytest.raises(ValueError, match="no pipelined form"):
        SimpleSolver(mesh, alpha=2, pipeline="on")
    with pytest.raises(ValueError, match="unknown pipeline"):
        PisoSolver(mesh, alpha=2, pipeline="yes")
    # and a pipelined executor has no steady outer loop
    with pytest.raises(ValueError, match="run_converged"):
        auto._exec.pipelined.run_converged(fresh(auto), DT, 10)


def test_pipeline_form_validation():
    """PipelineForm dataflow is validated at program construction: ring
    keys must be produced by some pipeline phase, and a ring needs a
    prime() to fill the prologue."""
    from repro.fvm.step_program import PipelineForm

    ok = Phase("double", "solve", ("x",), ("x",), lambda x: 2 * x)

    def build(pipeline):
        return StepProgram(phases=(ok,),
                           seed=lambda state, dt: {"x": state, "dt": dt},
                           finalize=lambda env: (env["x"], None),
                           seed_keys=("x", "dt"), pipeline=pipeline)

    build(PipelineForm(phases=(ok,)))  # fine: no ring
    with pytest.raises(ValueError, match="not produced"):
        build(PipelineForm(phases=(ok,), ring=("gradp",),
                           prime=lambda env: {"gradp": env["x"]}))
    with pytest.raises(ValueError, match="prime"):
        build(PipelineForm(
            phases=(ok, Phase("g", "assembly", ("x",), ("gradp",),
                              lambda x: x)),
            ring=("gradp",)))


def test_instrumented_sample_is_serial_provenance():
    """Instrumented samples force the serial schedule and say so: the
    PhaseBreakdown rows arrive with overlapped=False even when the
    session's advancing executor is the pipelined one — the controller
    calibrates the serial per-phase model from them."""
    s = PisoSolver(CavityMesh.cube(4, 2), alpha=2)   # auto -> pipelined
    assert s.pipelined
    _, _, sample = s.timed_step(fresh(s), DT)
    assert sample.overlapped is False
    exe = s.batched_executor(2)
    states = stack_states([s.initial_state(), s.initial_state()])
    _, _, rows = exe.timed_step(states,
                                jnp.asarray([1e-3, 2e-3], s.dtype))
    assert all(row.overlapped is False for row in rows)
    assert exe.samples == 1
