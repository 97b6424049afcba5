"""Seconds from process start to the first timed dispatch: imports, the
host plan, compilation or the cache's load, and the warm-up step."""


def read(run):
    return run["setup"]["total_s"]
