"""Seconds per PISO step: the window's wall time, from the first dispatch to
the end of its last completed step, over the steps completed."""


def read(run):
    ends = run["window"]["ends"]
    return ends[-1] / len(ends)
