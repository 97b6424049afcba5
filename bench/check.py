"""The comparison behind ``correct``: each compared step of the window is
run again by the plain reference from the program's own input state, and
the program's output is held against the reference's.

Three numbers, each the largest over the compared steps of ``max |program -
reference| / max |reference|`` over one group of fields: ``U_err`` (the
velocity), ``p_err`` (the pressure) and ``phi_err`` (every face flux: the
interior ones, both slabs' copies of each interface flux, and the z
boundary fluxes).  A field that is not finite reads :data:`NOT_FINITE`.
A step whose own solves report a failure fails the run whatever the
numbers read (:func:`verdict`).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from layout import interface_planes, to_dense
from reference import piso as ref

NUMBERS = ("U_err", "p_err", "phi_err")
# what a non-finite field reads: larger than any limit
NOT_FINITE = 1e300


def _rel(got: list, want: list) -> float:
    scale = max(float(np.max(np.abs(w))) for w in want)
    gap = 0.0
    for g, w in zip(got, want):
        if not np.all(np.isfinite(g)):
            return NOT_FINITE
        gap = max(gap, float(np.max(np.abs(g - w))))
    return gap / scale if scale > 0 else gap


def gaps(got: dict, want: dict, n: int, parts: int) -> dict:
    """The three numbers of one step: ``got`` is the program's output in
    the dense layout (:func:`layout.to_dense`), ``want`` the reference's."""
    want = {k: np.asarray(v, np.float64) for k, v in want.items()}
    planes = interface_planes(n, parts)
    fluxes = ("fx", "fy", "fz", "fb")
    return {
        "U_err": _rel([got["U"]], [want["U"]]),
        "p_err": _rel([got["p"]], [want["p"]]),
        "phi_err": _rel([got[k] for k in fluxes] + [got["fz_if_down"]],
                        [want[k] for k in fluxes] + [want["fz"][planes]]),
    }


def reference_step(dense_in: dict, box, n_correctors: int, dtype):
    """One reference step from a dense state, in ``dtype``; returns the
    dense output as host arrays and the solvers' report."""
    x = {k: jnp.asarray(dense_in[k], dtype) for k in ref.FIELDS}
    out, info = ref.step(x, box=box, n_correctors=n_correctors)
    out = {k: np.asarray(v) for k, v in out.items()}
    return out, {k: float(v) for k, v in info.items()}


def compare(pairs, box, n_correctors: int, n: int, parts: int,
            dtype=jnp.float64, control=None):
    """The three numbers over ``pairs`` of program states ``(input,
    output)`` (host arrays by field name).

    With ``control`` (a dtype), the program's output is not read: the
    reference computed in ``control`` takes its place, on the program's
    inputs.  Returns ``(numbers, report)`` with the largest reference
    residuals in ``report``."""
    worst = dict.fromkeys(NUMBERS, 0.0)
    report = {"ref_mom_res": 0.0, "ref_p_res": 0.0, "ref_p_iters": 0}
    for state_in, state_out in pairs:
        dense_in = to_dense(state_in, n, parts)
        want, info = reference_step(dense_in, box, n_correctors, dtype)
        if control is None:
            got = to_dense(state_out, n, parts)
        else:
            got, _ = reference_step(dense_in, box, n_correctors, control)
            got = {k: np.asarray(v, np.float64) for k, v in got.items()}
            got["fz_if_down"] = got["fz"][interface_planes(n, parts)]
        for k, v in gaps(got, want, n, parts).items():
            worst[k] = max(worst[k], v)
        report["ref_mom_res"] = max(report["ref_mom_res"], info["mom_res"])
        for i in range(n_correctors):
            report["ref_p_res"] = max(report["ref_p_res"], info[f"p_res_{i}"])
            report["ref_p_iters"] = max(report["ref_p_iters"],
                                        int(info[f"p_iters_{i}"]))
    return worst, report


def verdict(numbers: dict, limits: dict,
            failed: int) -> tuple[bool, dict]:
    """``correct`` and each number beside its limit: ``failed`` (the steps
    whose solves capped, diverged or did not converge, or that left a
    field not finite) has the limit 0, each of :data:`NUMBERS` the cell's
    own."""
    checks = {"failed_steps": {"value": int(failed), "limit": 0}}
    checks.update({k: {"value": numbers[k], "limit": float(limits[k])}
                   for k in NUMBERS})
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
