"""Process-level accelerator environment tuning (XLA flags, platform pin).

The software-pipelined executor (:mod:`repro.fvm.step_program`) expresses
the assemble/solve overlap as *dataflow* — independent ops inside one XLA
program.  Whether the runtime actually executes them concurrently is up to
XLA's scheduler: on GPU the latency-hiding scheduler and the
highest-priority async stream must be enabled for the compiler to place
step t+1's assembly on a stream that runs under step t's pressure solve.
These are process-wide ``XLA_FLAGS`` that MUST be set before the first
JAX backend initialization — after that they are silently ignored, which
is exactly the failure mode this module exists to prevent (it raises
instead).

Usage — call :func:`configure_platform` first thing in a launch script::

    from repro.env import configure_platform
    configure_platform()          # or configure_platform("gpu")
    import jax                    # safe: flags are already in the env

The helper is idempotent (re-running a launcher in one process, a test
calling it twice) and merge-safe: flags the user already set in
``XLA_FLAGS`` win — only *absent* flags are appended, keyed by flag name.
"""
from __future__ import annotations

import os
import sys

__all__ = ["GPU_XLA_FLAGS", "configure_platform", "enable_x64",
           "enable_compile_cache", "jax_initialized", "COMPILE_CACHE_DIR"]

# JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is
# unset: a fixed directory of the checkout (listed in .gitignore), so a
# later process on the same checkout finds what an earlier one compiled
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")

# The overlap-relevant XLA tuning set (GPU backend).  The latency-hiding
# scheduler + async/priority-stream flags are what let the pipelined
# program's independent assembly and solve ops actually run concurrently;
# the triton fusion flags are the standard companions for keeping the
# assembly side in few large kernels instead of many small ones.
GPU_XLA_FLAGS: tuple[str, ...] = (
    "--xla_gpu_enable_triton_softmax_fusion=true",
    "--xla_gpu_triton_gemm_any=True",
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def jax_initialized() -> bool:
    """True once any JAX backend has been initialized in this process.

    Flag changes after this point are ignored by XLA, so callers use this
    to fail loudly instead of silently tuning nothing.  Detection is
    best-effort against JAX internals (``xla_bridge``'s backend table);
    an unimported jax is by definition uninitialized.
    """
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    try:
        from jax._src import xla_bridge

        return bool(xla_bridge._backends)
    except Exception:
        # unknown JAX internals: conservatively treat "jax imported" as
        # "may be initialized" only if we cannot tell at all
        return False


def enable_x64() -> None:
    """Turn on double-precision JAX arrays for this process.

    The solver's baseline numerics are f64 (the paper's; mixed-precision
    policies refine *against* an f64 outer residual, so they need it
    too).  Every entry point — launchers, the pytest conftest, the
    benchmark subprocess cells — calls this one helper instead of
    scattering ``jax.config.update("jax_enable_x64", True)`` strings.
    Unlike :data:`GPU_XLA_FLAGS` this is a JAX-level config, safe to set
    (idempotently) at any time, including after backend initialization.
    """
    import jax

    jax.config.update("jax_enable_x64", True)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as it stands (JAX
    reads it itself) and no other directory is set in code; otherwise the
    cache sits at :data:`COMPILE_CACHE_DIR`.  Every launcher and the chip
    smoke call this one helper.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def _flag_name(flag: str) -> str:
    return flag.split("=", 1)[0]


_GPU_NAMES = ("gpu", "cuda", "rocm")


def configure_platform(platform: str | None = None,
                       flags: tuple[str, ...] = GPU_XLA_FLAGS) -> str:
    """Merge ``flags`` into ``XLA_FLAGS`` (and optionally pin a platform).

    Must run before JAX initializes a backend — raises ``RuntimeError``
    otherwise, because XLA reads the env exactly once.  Idempotent: flags
    whose ``--name`` is already present in ``XLA_FLAGS`` are left alone
    (so a user override like ``--xla_gpu_enable_latency_hiding_scheduler=
    false`` survives), and a second call is a no-op.  ``platform``
    ("cpu" | "gpu" | "tpu") soft-pins ``JAX_PLATFORMS`` via ``setdefault``
    — an explicit user env wins.  Returns the final ``XLA_FLAGS`` string.

    The GPU flag set is applied only when the *resolved* platform (the
    ``platform`` argument, else ``JAX_PLATFORMS``) names a GPU backend:
    XLA hard-aborts the process on flags its build does not register, and
    the ``--xla_gpu_*`` set comes with the GPU plugin — on a CPU/TPU
    platform (or when no platform is declared at all) the call degrades
    to a flag-preserving no-op instead of poisoning ``XLA_FLAGS``.
    """
    if jax_initialized():
        raise RuntimeError(
            "configure_platform() called after JAX backend initialization "
            "— XLA_FLAGS are read once at startup and changes now would be "
            "silently ignored. Call it before the first jax array/op (or "
            "before importing modules that create one).")
    resolved = platform or os.environ.get("JAX_PLATFORMS", "")
    gpu_target = any(name in resolved.lower() for name in _GPU_NAMES)
    current = os.environ.get("XLA_FLAGS", "")
    merged = [tok for tok in current.split() if tok]
    if gpu_target:
        present = {_flag_name(tok) for tok in merged}
        merged += [f for f in flags if _flag_name(f) not in present]
    final = " ".join(merged)
    os.environ["XLA_FLAGS"] = final
    if platform is not None:
        os.environ.setdefault("JAX_PLATFORMS", platform)
    return final
