"""Session supervision (ISSUE 8): the divergence state machine, the
deterministic fault harness, compiled health signals, and exact engine
checkpoint/restore — including the subprocess kill-and-resume gate."""
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from repro.faults import KINDS, ChaosMonkey, parse_kinds
from repro.fvm.mesh import CavityMesh
from repro.fvm.piso import StepStats
from repro.fvm.step_program import health_flags
from repro.serving.engine import SimulationEngine
from repro.serving.supervisor import (DEGRADED, FAILED, HEALTHY,
                                      QUARANTINED, SessionSupervisor,
                                      SupervisorConfig, window_verdict)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# the state machine, engine-free
# ---------------------------------------------------------------------------

def test_escalation_ladder_and_fail():
    sup = SessionSupervisor(SupervisorConfig(retry_budget=3))
    assert sup.state == HEALTHY and sup.dt_scale == 1.0
    assert sup.on_fault("diverged", 8) == "retry"
    assert sup.state == DEGRADED and sup.dt_scale == 0.5
    assert sup.on_fault("diverged", 8) == "quarantine"
    assert sup.state == QUARANTINED and sup.dt_scale == 0.25
    assert sup.on_fault("hit_cap", 8) == "retry"     # budget not yet spent
    assert sup.state == QUARANTINED
    assert sup.on_fault("hit_cap", 8) == "fail"      # 4th fault > budget 3
    assert sup.state == FAILED
    kinds = [e.kind for e in sup.events]
    assert kinds == ["fault", "degrade", "fault", "quarantine", "fault",
                     "fault", "fail"]


def test_recovery_ladder_resets_budget_and_dt():
    sup = SessionSupervisor(SupervisorConfig(recovery_windows=2))
    sup.on_fault("diverged", 4)
    sup.on_fault("diverged", 4)
    assert sup.state == QUARANTINED and sup.retries_used == 2
    assert sup.on_clean_window(8) == "none"
    assert sup.on_clean_window(12) == "recover"      # -> DEGRADED
    assert sup.state == DEGRADED
    # a fresh fault resets the clean streak
    assert sup.on_clean_window(16) == "none"
    sup.on_fault("diverged", 16)
    assert sup.state == QUARANTINED and sup.clean_windows == 0
    for step in (20, 24):
        sup.on_clean_window(step)
    assert sup.state == DEGRADED
    for step in (28, 32):
        out = sup.on_clean_window(step)
    assert out == "restore" and sup.state == HEALTHY
    assert sup.dt_scale == 1.0 and sup.retries_used == 0
    # healthy windows are free: no counter churn, no events
    assert sup.on_clean_window(36) == "none"


def test_rollback_returns_fresh_copies():
    sup = SessionSupervisor()
    state = {"U": jnp.ones(4)}
    sup.checkpoint(state, 12)
    s1, n1 = sup.rollback()
    s2, n2 = sup.rollback()
    assert n1 == n2 == 12
    assert s1["U"] is not s2["U"] and s1["U"] is not state["U"]
    np.testing.assert_array_equal(np.asarray(s1["U"]), 1.0)


def test_supervisor_dict_roundtrip():
    sup = SessionSupervisor(SupervisorConfig(retry_budget=5,
                                             fallback_backend="reference"))
    sup.on_fault("diverged", 8)
    sup.orig_backend = "auto"
    sup.checkpoint({"U": jnp.zeros(2)}, 8)
    d = sup.to_dict()
    assert d["last_good_step"] == 8
    back = SessionSupervisor.from_dict(d)
    assert back.state == DEGRADED and back.dt_scale == 0.5
    assert back.retries_used == 1 and back.orig_backend == "auto"
    assert back.config == sup.config
    assert [e.kind for e in back.events] == [e.kind for e in sup.events]
    assert back.to_dict()["events"] == d["events"]


def test_window_verdict_semantics():
    def stats(diverged, hit_cap):
        return StepStats(
            mom_iters=jnp.zeros(4), p_iters=jnp.zeros((4, 2)),
            p_outer=jnp.zeros((4, 2)),
            continuity_err=jnp.zeros(4), p_residual=jnp.zeros(4),
            converged=jnp.ones(4, bool) & ~jnp.asarray(diverged),
            diverged=jnp.asarray(diverged), hit_cap=jnp.asarray(hit_cap))

    clean = [False] * 4
    assert window_verdict(stats(clean, clean)) is None
    assert window_verdict(stats([False, True, False, False],
                                clean)) == "diverged"
    # one grazed cap in an otherwise clean window is tolerated...
    assert window_verdict(stats(clean, [True, False, False, False])) is None
    # ...but a whole window at the cap is the stuck-solver signature
    assert window_verdict(stats(clean, [True] * 4)) == "hit_cap"
    # divergence outranks the cap
    assert window_verdict(stats([True] * 4, [True] * 4)) == "diverged"


def test_health_flags_reduction():
    state = {"U": jnp.ones((2, 3)), "p": jnp.zeros(5)}
    t = jnp.asarray(True)
    f = jnp.asarray(False)
    ok, div, cap = health_flags(state, t, f, jnp.asarray(0.5))
    assert bool(ok) and not bool(div) and not bool(cap)
    # a non-finite leaf flips diverged and suppresses converged/hit_cap
    bad = {"U": state["U"].at[0, 0].set(jnp.inf), "p": state["p"]}
    ok, div, cap = health_flags(bad, t, t, jnp.asarray(0.5))
    assert not bool(ok) and bool(div) and not bool(cap)
    # a non-finite auxiliary scalar counts too (residual blow-up)
    ok, div, cap = health_flags(state, t, f, jnp.asarray(jnp.nan))
    assert not bool(ok) and bool(div)
    # solver cap with finite state: hit_cap, not diverged
    ok, div, cap = health_flags(state, f, t, jnp.asarray(0.5))
    assert not bool(ok) and not bool(div) and bool(cap)


# ---------------------------------------------------------------------------
# the deterministic fault harness
# ---------------------------------------------------------------------------

def test_parse_kinds():
    assert parse_kinds("all") == KINDS
    assert parse_kinds("nan,cap") == ("nan", "cap")
    with pytest.raises(ValueError, match="gremlin"):
        parse_kinds("nan,gremlin")


def test_chaos_schedule_is_seeded_and_sorted():
    a = ChaosMonkey(7, ["a", "b", "c", "d"], horizon=16)
    b = ChaosMonkey(7, ["a", "b", "c", "d"], horizon=16)
    c = ChaosMonkey(8, ["a", "b", "c", "d"], horizon=16)
    assert a.events == b.events
    assert a.events != c.events
    assert len(a.events) == 2               # one per two sessions
    assert a.events == sorted(a.events, key=lambda e: (e.step, e.sid))
    assert all(1 <= e.step < 16 and e.kind in KINDS for e in a.events)


def test_chaos_poke_fires_once_and_skips_closed_targets():
    class Sess:
        steps_done = 4

    class Eng:
        sessions = {"a": Sess()}

    monkey = ChaosMonkey(0, ["a", "gone"], kinds=("slow",), n_events=4,
                         horizon=3)

    class Ctl:
        def step(self, sample):
            return sample

    Sess.controller = Ctl()
    fired = monkey.poke(Eng())
    assert fired == [e for e in monkey.events if e.sid == "a"]
    assert monkey.poke(Eng()) == []         # every event fired or moot
    assert len(monkey._done) == len(monkey.events)


# ---------------------------------------------------------------------------
# engine integration: persistent cap fault -> quarantine -> clean failure
# ---------------------------------------------------------------------------

def _break_pressure_solver(sess):
    """An operator pushing a bad config: unreachable tolerance at a tiny
    iteration cap — every pressure solve from now on exits at maxiter."""
    sess.solver.p_tol = 1e-30
    sess.solver.p_maxiter = 2
    sess.solver._programs.clear()
    sess.solver.rebind_alpha(sess.solver.alpha)


def test_persistent_cap_fault_fails_cleanly():
    """A fault that survives rollback (solver misconfiguration) burns the
    whole retry budget and FAILS: the engine closes the session, parks the
    post-mortem in engine.failed, and step_all returns without hanging."""
    mesh = CavityMesh.cube(4, 2)
    cfg = SupervisorConfig(retry_budget=2)
    eng = SimulationEngine(scan_window=4, supervise=True,
                           supervisor_config=cfg)
    eng.open_session("a", mesh, dt=1e-3, alpha0=2, adaptive=False)
    eng.open_session("b", mesh, dt=2e-3, alpha0=2, adaptive=False)
    eng.step_all(4)
    _break_pressure_solver(eng.sessions["a"])
    eng.step_all(8)
    assert "a" not in eng.sessions and "a" in eng.failed
    post = eng.failed["a"]
    kinds = [e["kind"] for e in post["events"]]
    assert kinds == ["fault", "degrade", "fault", "quarantine", "fault",
                     "fail"]
    assert all(e["detail"] == "hit_cap" for e in post["events"]
               if e["kind"] == "fault")
    # the healthy tenant was never disturbed
    assert eng.sessions["b"].steps_done == 12
    assert eng.sessions["b"].supervisor.state == HEALTHY
    assert eng.stats()["failed"] == ["a"]


def test_quarantine_applies_and_recovery_restores_fallback_backend():
    """QUARANTINED rebinds the session's Krylov backend to the configured
    fallback; recovering back to DEGRADED restores the original."""
    mesh = CavityMesh.cube(4, 2)
    cfg = SupervisorConfig(retry_budget=10, recovery_windows=2,
                           fallback_backend="reference")
    eng = SimulationEngine(scan_window=4, supervise=True,
                           supervisor_config=cfg)
    eng.open_session("a", mesh, dt=1e-3, alpha0=2, adaptive=False)
    eng.step_all(4)
    s = eng.sessions["a"]

    def poison():
        s.state = s.state._replace(U=s.state.U.at[0, 0, 0].set(jnp.nan))

    poison()
    eng.step_all(4)              # fault 1 -> DEGRADED, clean retry (1/2)
    assert s.supervisor.state == DEGRADED
    poison()
    eng.step_all(4)              # fault 2 -> QUARANTINED + fallback
    assert s.supervisor.state == QUARANTINED
    assert s.solver.solver_backend == "reference"
    assert s.controller.solver_backend == "reference"
    assert s.supervisor.orig_backend == "auto"
    eng.step_all(4)              # clean (2/2): recover -> DEGRADED
    assert s.supervisor.state == DEGRADED
    assert s.solver.solver_backend == "auto"
    eng.step_all(4)              # clean (1/2)
    eng.step_all(4)              # clean (2/2): restore -> HEALTHY
    assert s.supervisor.state == HEALTHY
    assert s.supervisor.dt_scale == 1.0 and s.supervisor.retries_used == 0
    assert s.steps_done == 6 * 4
    assert np.isfinite(np.asarray(s.state.U)).all()


# ---------------------------------------------------------------------------
# exact checkpoint/restore
# ---------------------------------------------------------------------------

def test_snapshot_restore_bitwise_resume(tmp_path):
    """Mid-run snapshot -> restore resumes bit-for-bit: states, step
    counters, controller calibration and supervisor state all survive, and
    the next window out of the restored engine matches the original
    exactly (0.0, not just <= 1e-10)."""
    mesh = CavityMesh.cube(4, 4)
    eng = SimulationEngine(scan_window=4, supervise=True)
    eng.open_session("a", mesh, dt=1e-3, alpha0=2, adaptive=True)
    eng.open_session("b", mesh, dt=2e-3, alpha0=2, adaptive=False)
    eng.step_all(8)
    # a degraded session's supervisor state must survive the round-trip
    sb = eng.sessions["b"]
    sb.state = sb.state._replace(U=sb.state.U.at[0, 0, 0].set(jnp.nan))
    eng.step_all(4)
    assert sb.supervisor.state == DEGRADED

    snap = str(tmp_path / "snap")
    eng.snapshot(snap)
    eng2 = SimulationEngine.restore(snap)

    for sid in ("a", "b"):
        s1, s2 = eng.sessions[sid], eng2.sessions[sid]
        assert s2.steps_done == s1.steps_done
        assert s2.controller.alpha == s1.controller.alpha
        assert s2.controller.calibration.n_obs == \
            s1.controller.calibration.n_obs
        sup1, sup2 = s1.supervisor, s2.supervisor
        assert (sup2.state, sup2.dt_scale, sup2.retries_used) == \
            (sup1.state, sup1.dt_scale, sup1.retries_used)
        assert [e.kind for e in sup2.events] == \
            [e.kind for e in sup1.events]
        # the last-good checkpoint arrays ride the npz
        g1, n1 = sup1.last_good
        g2, n2 = sup2.last_good
        assert n1 == n2
        assert float(jnp.abs(g2.U - g1.U).max()) == 0.0
    # both engines advance one more window: bitwise identical
    eng.step_all(4)
    eng2.step_all(4)
    for sid in ("a", "b"):
        d = float(jnp.abs(eng2.sessions[sid].state.U
                          - eng.sessions[sid].state.U).max())
        assert d == 0.0
        assert eng2.sessions[sid].controller.alpha == \
            eng.sessions[sid].controller.alpha


def test_restore_missing_path_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        SimulationEngine.restore(str(tmp_path / "nope"))


# ---------------------------------------------------------------------------
# kill-and-resume through the serving CLI (mirrors test_fault_tolerance)
# ---------------------------------------------------------------------------

def run_serve(extra, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    cmd = [sys.executable, "-m", "repro.launch.serve", "--cfd-n", "4",
           "--parts", "2", "--scan-steps", "4", "--adaptive", *extra]
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=timeout, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def digests(out):
    return sorted(l.split()[1:] for l in out.splitlines()
                  if l.startswith("digest "))


def test_serve_kill_and_resume_digest_parity(tmp_path):
    """The CI chaos-smoke gate, in-process: an uninterrupted supervised
    run vs a run killed at a window-aligned snapshot and resumed from it
    — the per-session state digests must match exactly."""
    full = run_serve(["--sessions", "2", "--steps", "8", "--supervise",
                      "--snapshot-dir", str(tmp_path / "full")])
    assert "supervision: healthy=2" in full
    run_serve(["--sessions", "2", "--steps", "4", "--supervise",
               "--snapshot-dir", str(tmp_path / "part")])
    resumed = run_serve(["--resume", "--steps", "8",
                         "--snapshot-dir", str(tmp_path / "part")])
    assert "resumed 2 sessions" in resumed
    d_full, d_res = digests(full), digests(resumed)
    assert d_full and d_full == d_res
