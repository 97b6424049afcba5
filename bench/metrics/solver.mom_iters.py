"""Momentum BiCGStab iterations per step (``StepStats.mom_iters``, the
largest of the three components), mean over the window."""


def read(run):
    its = run["window"]["mom_iters"]
    return float(sum(its)) / len(its)
