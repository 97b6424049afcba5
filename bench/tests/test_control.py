"""``correct`` fails where it should: the lower-precision control and the
faults a cell can have, at a size a test run holds (10³ on the CPU, the
chip check skipped).  The same readings at the cells' own size come from
``bench/calibrate.py`` on the chip (PERF.md)."""
import os

import jax
import jax.numpy as jnp
import pytest

from conftest import TINY, make_root

FAULTS = ("unchanged", "altered", "no_exchange", "capped")


def test_sound_run_is_correct(tiny_root, drive):
    line, err = drive(tiny_root, "--workload", TINY, "--seed", "5",
                      "--seconds", "0.3", "--trace", "0")
    assert line["correct"] is True, err
    assert line["failed"] == 0
    tail = err.strip().splitlines()[-4:]
    assert [line.split()[1] for line in tail] == [
        "failed_steps", "U_err", "p_err", "phi_err"]


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_reads_incorrect(fault, tiny_root, drive, monkeypatch):
    """The timed path broken underneath the harness: a step that returns
    its state unchanged, one velocity altered where it is produced, the
    exchange of interface fluxes between slabs left out, a solve that
    reports its cap hit with fields the limits would pass."""
    from repro.fvm.piso import SegregatedSolver

    real = SegregatedSolver.run_steps

    def broken(self, state, dt, n_steps):
        if fault == "unchanged":
            _, stats = real(self, jax.tree.map(jnp.copy, state), dt,
                            n_steps)
            return state, stats
        out, stats = real(self, state, dt, n_steps)
        if fault == "altered":
            U = out.U.at[0, 0, 0].add(1e-6 * jnp.max(jnp.abs(out.U)))
            return out._replace(U=U), stats
        if fault == "capped":
            return out, stats._replace(hit_cap=jnp.ones_like(stats.hit_cap))
        return out._replace(phi_if=jnp.zeros_like(out.phi_if)), stats

    monkeypatch.setattr(SegregatedSolver, "run_steps", broken)
    line, err = drive(tiny_root, "--workload", TINY, "--seed", "6",
                      "--seconds", "0.2", "--trace", "0")
    assert line["correct"] is False, err


@pytest.mark.parametrize("config", ["cavity3d", "backstep3d"])
def test_control_fails_the_limits(config, tmp_path):
    """The program reads within every limit; the reference computed in
    float32 in its place fails at least one."""
    import calibrate
    import run
    import spec
    from reference.piso import box_from

    root = make_root(tmp_path, n=10, config=config)
    cell = spec.load_cell(root, TINY, bench=os.path.join(root, "bench"))
    box = box_from(cell.config, cell.traffic["co"])
    solver = run.build(cell)
    r = calibrate.readings(jax, solver, box, cell, seed=7, steps=3,
                           control=True)
    assert r["program_correct"] is True, r
    assert r["control_correct"] is False, r
