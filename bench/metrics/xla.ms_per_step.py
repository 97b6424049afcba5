"""Device time of every operation that is not a Pallas kernel (assembly,
value update, f64 refinement and the Krylov glue, all in XLA), per traced
step, in ms."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["steps"]:
        return None
    return 1e3 * tr["xla_s"] / tr["steps"]
