"""The main-path Pallas kernels compile for a TPU v5e at deployment size.

Ahead-of-time compiles for a *described* ``v5e:2x2`` chip (no chip
attached): the TPU compiler refuses what interpret mode accepts — unaligned
dynamic loads, one-element blocks, i64 index maps under x64, f64 custom
calls — so these compiles guard the layout contract of ``spmv_dia`` and
``krylov_fused`` at no chip time.  The size is one coarse part of the
paper's 210³ lidDrivenCavity3D at ``--parts 10 --alpha 2``: ``m =
210 * 210 * 21 * 2 = 1,852,200`` rows with ``x_pad`` resident in VMEM.
The assembly's face sums compile too, at the benchmark's 70³, as gathers
with no scatter.

The topology is described inside a fixture (never at import): only the
worker that runs this file loads the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.fvm.assembly import CavityAssembly
from repro.fvm.mesh import CavityMesh
from repro.kernels.krylov_fused.krylov_fused import (
    fused_axpy_precond_single, spmv_dot_single)
from repro.kernels.spmv_dia.spmv_dia import pick_block_rows, spmv_dia_single

N, PARTS, ALPHA = 210, 10, 2
PLANE = N * N
M = PLANE * (N // PARTS) * ALPHA
OFFSETS = (-PLANE, -N, -1, 0, 1, N, PLANE)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described-chip compile is written to a persistent cache but can
    # never be read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _lower(kernel, one_chip, dtype):
    def shape(s, dt=dtype):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    br = pick_block_rows(M, dtype)
    band = dict(offsets=OFFSETS, plane=PLANE, block_rows=br,
                accum_dtype="float32")
    if kernel == "spmv_dia_single":
        return spmv_dia_single.lower(shape((7, M)), shape((M + 2 * PLANE,)),
                                     **band)
    if kernel == "spmv_dot_single":
        return spmv_dot_single.lower(shape((7, M)), shape((M + 2 * PLANE,)),
                                     **band)
    vec = shape((M,))
    return fused_axpy_precond_single.lower(
        vec, vec, vec, vec, vec, shape((), jnp.float32), block_rows=br,
        accum_dtype="float32")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kernel", ["spmv_dia_single", "spmv_dot_single",
                                    "fused_axpy_precond_single"])
def test_kernel_compiles_for_v5e(kernel, dtype, one_chip):
    assert jax.config.jax_enable_x64   # conftest's setting: no i64 leaks
    compiled = _lower(kernel, one_chip, dtype).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_f64_bands_refused_before_lowering(one_chip):
    f64 = jax.ShapeDtypeStruct((7, M), jnp.float64, sharding=one_chip)
    xp = jax.ShapeDtypeStruct((M + 2 * PLANE,), jnp.float64,
                              sharding=one_chip)
    with pytest.raises(ValueError, match="f64 custom call"):
        spmv_dot_single.lower(f64, xp, offsets=OFFSETS, plane=PLANE)


def test_assembly_face_sums_compile_without_scatter(one_chip):
    """The assembly's face sums compile for a v5e as gathers: the gradient
    of the benchmark's 70³ cavity on 10 slabs (f64, as the assembly runs)
    holds no scatter instruction."""
    asm = CavityAssembly(CavityMesh.cube(70, 10), nu=1e-3)
    p = jax.ShapeDtypeStruct((10, asm.m), jnp.float64, sharding=one_chip)
    hlo = jax.jit(asm.grad).lower(p).compile().as_text()
    assert re.search(r"[ )]gather\(", hlo)
    assert not re.findall(r"[ )]scatter\(", hlo)
