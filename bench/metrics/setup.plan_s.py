"""Seconds of ``make_solver`` on the host clock: mesh, LDU addressing,
repartition and value-update plans, program binding."""


def read(run):
    return run["setup"]["plan_s"]
