#!/usr/bin/env python3
"""Compile each cell's step for a described TPU v5e, with no chip attached.

    JAX_PLATFORMS=cpu python bench/rehearse.py [workload ...]

Builds every named cell (all of ``BENCHMARK.json`` by default) as the
benchmark builds it, but with JAX told that its backend is a TPU, so that
the solver takes the path it takes on the chip (the fused Pallas kernels
compiled by Mosaic, not interpreted), then compiles one step for one chip
of a described ``v5e:2x2`` and prints ``memory_analysis()``.  What the
chip's compiler refuses, or a program that does not fit the chip's memory,
shows here at no chip time.  A compile that passes is not a chip run.
"""
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def rehearse(workload: str, one_chip) -> dict:
    import jax
    import jax.numpy as jnp

    import run
    import spec

    cell = spec.load_cell(ROOT, workload)
    solver = run.build(cell)
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(solver.initial_state))
    dt = jax.ShapeDtypeStruct((), jnp.float64, sharding=one_chip)
    t = time.perf_counter()
    compiled = solver._stepper.lower_step(shapes, dt)
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    return {"workload": workload, "backends": solver.describe_backends(),
            "compile_s": round(time.perf_counter() - t, 1),
            "temp_bytes": mem.temp_size_in_bytes,
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
            "tpu_custom_calls": text.count("tpu_custom_call")}


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.env import enable_x64

    enable_x64()
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    # a described chip's compile cannot be read back from a persistent
    # cache without the chip: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = argv or [w["name"] for w in json.load(f)["workloads"]]
    # the solver picks its backend and kernel lowering by asking JAX for
    # the platform: answer as the chip would
    jax.default_backend = lambda: "tpu"
    for name in names:
        print(json.dumps(rehearse(name, one_chip)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
