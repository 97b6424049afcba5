"""The CFD launcher reports every step's Krylov health and fails on a
capped or diverged solve instead of printing it as a result."""
import jax.numpy as jnp
import pytest

import repro.launch.case as case
from repro.fvm.piso import StepStats


def _stats(converged, diverged, hit_cap):
    z = jnp.zeros(())
    return StepStats(mom_iters=z, p_iters=jnp.zeros((2,)),
                     p_outer=jnp.zeros((2,)), continuity_err=z,
                     p_residual=z, converged=jnp.asarray(converged),
                     diverged=jnp.asarray(diverged),
                     hit_cap=jnp.asarray(hit_cap))


@pytest.mark.parametrize("flags,want", [
    ((True, False, False), "ok"),
    ((False, False, True), "CAPPED"),
    ((False, True, False), "DIVERGED"),
    ((False, False, False), "UNCONVERGED"),
])
def test_solve_health(flags, want):
    assert case.solve_health(_stats(*flags)) == want
    window = _stats(*(jnp.asarray([True, v]) for v in flags))
    assert case.solve_health(window, 1) == want


def test_launcher_exits_nonzero_on_a_capped_solve(monkeypatch, capsys):
    real = case.make_solver
    monkeypatch.setattr(case, "make_solver",
                        lambda *a, **kw: real(*a, p_maxiter=2, **kw))
    # no persistent compile cache from inside the test run
    monkeypatch.setattr(case, "enable_compile_cache", lambda: None)
    with pytest.raises(SystemExit, match="CAPPED"):
        case.main(["--n", "4", "--parts", "2", "--alpha", "2",
                   "--steps", "1"])
    assert "solves=CAPPED" in capsys.readouterr().out
