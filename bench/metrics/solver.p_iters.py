"""Pressure CG iterations per step (``StepStats.p_iters`` summed over the
correctors; inner iterations under a refined policy), mean over the
window."""


def read(run):
    its = run["window"]["p_iters"]
    return float(sum(its)) / len(its)
