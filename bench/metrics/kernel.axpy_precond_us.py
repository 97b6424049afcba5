"""Device microseconds per call of the fused axpy-pair + Jacobi + dots
kernel in the coarse pressure CG (one call per inner iteration)."""
from kernel_calls import per_call_us


def read(run):
    return per_call_us(run, "axpy_precond")
