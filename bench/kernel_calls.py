"""Device time per call of a Pallas kernel of a cell's coarse pressure
solve, from the reduced trace (:mod:`xplane`).

The reduction keeps the calls of each kernel kind by the shape of their
first output.  The Krylov kernels run on the stacked coarse parts, so the
calls of the pressure solve are those whose leading dimension is the
number of coarse parts, ``parts // alpha``, whatever row layout the
kernels use.
"""
from __future__ import annotations


def per_call_us(run: dict, kind: str):
    """Mean device microseconds per call of ``kind`` on the coarse
    pressure system in the traced window, or None where there is none."""
    cell = run["cell"]
    coarse = cell.config["parts"] // cell.traffic["alpha"]
    calls = seconds = 0
    for shape, k in (run["trace"] or {}).get("kernels", {}).get(
            kind, {}).items():
        if int(shape.split("x")[0]) == coarse:
            calls += k["calls"]
            seconds += k["seconds"]
    return 1e6 * seconds / calls if calls else None
