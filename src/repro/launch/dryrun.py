import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count on first init).  Everything below is ordinary.
import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

from repro.compat import cost_analysis_dict  # noqa: E402

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. lowers the right step (train_step / prefill / serve_step) against
     ShapeDtypeStruct inputs (zero allocation),
  3. compiles, prints ``memory_analysis()`` (proves the per-device footprint
     fits) and ``cost_analysis()`` (FLOPs/bytes for the roofline),
  4. parses the partitioned HLO for collective ops (all-gather/all-reduce/
     reduce-scatter/all-to-all/collective-permute) and sums their bytes —
     cost_analysis does not report them,
  5. writes one JSON per cell under --out.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k \
      --mesh single_pod --out results/dryrun
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|f8e4m3|f8e5m2|s64|s32|s16|s8|u64"
                       r"|u32|u16|u8|pred|c64|c128)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of every typed shape literal in `text`."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-device collective bytes/counts by op type from partitioned HLO."""
    stats = {op: {"count": 0, "bytes": 0} for op in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"(?:ROOT )?%?[\w.\-]+ = (.*?) (\S+)\(", ls)
        if not m:
            continue
        result_part, opname = m.groups()
        opname = opname.split(".")[0]
        for op in _COLLECTIVES:
            if opname == op or opname.startswith(op + "-"):
                # `-start` variants carry the payload; `-done` repeats the
                # shape — count only starts and plain (synchronous) forms.
                if opname.endswith("-done"):
                    continue
                stats[op]["count"] += 1
                stats[op]["bytes"] += _shape_bytes(result_part)
                break
    stats["total_bytes"] = sum(
        v["bytes"] for k, v in stats.items() if isinstance(v, dict))
    stats["total_count"] = sum(
        v["count"] for k, v in stats.items() if isinstance(v, dict))
    return stats


def build_lowerable(arch: str, shape_name: str, mesh, cfg=None):
    """Return (fn, args, in_shardings, donate) for jax.jit lowering."""
    import jax
    from repro.configs.registry import get_config, input_specs
    from repro.configs.shapes import SHAPES
    from repro.models import lm
    from repro.models.sharding import (batch_shardings, cache_shardings,
                                       param_shardings)
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.training.optimizer import AdamW, AdamWState
    from repro.training.train_step import make_train_step, state_specs
    from repro.training.train_step import TrainState

    cfg = cfg or get_config(arch)
    from repro.models.sharding import set_activation_mesh, set_sp_outputs
    set_activation_mesh(mesh)  # enable in-model activation constraints
    set_sp_outputs(cfg.sp_reduce_scatter)
    spec = SHAPES[shape_name]
    specs = input_specs(arch, shape_name, cfg)
    p_specs = lm.param_specs(cfg)
    p_sh = param_shardings(mesh, p_specs)

    if spec.kind == "train":
        opt = AdamW()
        st_specs = state_specs(cfg, opt)
        st_sh = TrainState(
            params=p_sh,
            opt=AdamWState(step=NamedSharding(mesh, PartitionSpec()),
                           m=param_shardings(mesh, st_specs.opt.m),
                           v=param_shardings(mesh, st_specs.opt.v)),
            err=None)
        batch = {k: specs[k] for k in specs}
        b_sh = batch_shardings(mesh, batch)
        fn = make_train_step(cfg, opt, grad_shardings=p_sh)
        return fn, (st_specs, batch), (st_sh, b_sh), 0, (st_sh, None)
    if spec.kind == "prefill":
        tokens = specs["tokens"]
        b_sh = batch_shardings(mesh, {k: v for k, v in specs.items()})
        max_len = spec.seq_len + (cfg.frontend_len
                                  if cfg.frontend == "vision_stub" else 0)

        def fn(params, tokens, frontend=None):
            return lm.prefill(cfg, params, tokens, max_len,
                              frontend=frontend)

        args = (p_specs, tokens) + ((specs["frontend"],)
                                    if "frontend" in specs else ())
        shardings = (p_sh, b_sh["tokens"]) + ((b_sh["frontend"],)
                                              if "frontend" in specs else ())
        mem_len = cfg.frontend_len if cfg.cross_attention else 0
        c_out = cache_shardings(
            mesh, lm.cache_specs(cfg, spec.global_batch, max_len,
                                 memory_len=mem_len))
        return fn, args, shardings, None, (None, c_out)
    # decode
    cache = specs["cache"]
    c_sh = cache_shardings(mesh, cache)
    b_sh = batch_shardings(mesh, {"tokens_last": specs["tokens_last"],
                                  "pos": specs["pos"]})

    def fn(params, cache, tokens_last, pos):
        return lm.decode_step(cfg, params, cache, tokens_last, pos)

    return (fn, (p_specs, cache, specs["tokens_last"], specs["pos"]),
            (p_sh, c_sh, b_sh["tokens_last"], b_sh["pos"]), 1, (None, c_sh))


def _measure(arch, shape_name, mesh, cfg):
    """Lower+compile a (reduced) config; return (flops, bytes, collectives)."""
    import jax

    fn, args, shardings, donate, out_sh = build_lowerable(
        arch, shape_name, mesh, cfg)
    jk = {"in_shardings": shardings}
    if donate is not None:
        jk["donate_argnums"] = donate
    if out_sh is not None:
        jk["out_shardings"] = out_sh
    with mesh:
        compiled = jax.jit(fn, **jk).lower(*args).compile()
        cost = cost_analysis_dict(compiled)
        col = parse_collectives(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)), col)


def scan_corrected(arch, shape_name, mesh, record):
    """Two/three-point extrapolation for scan-body undercount (see
    launch/analysis.py docstring).  Writes corrected per-device numbers."""
    import dataclasses as dc

    from repro.configs.registry import get_config

    cfg = get_config(arch)
    plen = len(cfg.period())
    v1 = dc.replace(cfg, n_layers=plen,
                    encoder_layers=min(1, cfg.encoder_layers))
    v2 = dc.replace(cfg, n_layers=2 * plen,
                    encoder_layers=min(1, cfg.encoder_layers))
    f1, b1, c1 = _measure(arch, shape_name, mesh, v1)
    f2, b2, c2 = _measure(arch, shape_name, mesh, v2)
    n = cfg.n_periods
    flops = f1 + (n - 1) * (f2 - f1)
    byts = b1 + (n - 1) * (b2 - b1)
    col = c1["total_bytes"] + (n - 1) * (c2["total_bytes"] - c1["total_bytes"])
    cnt = c1["total_count"] + (n - 1) * (c2["total_count"] - c1["total_count"])
    if cfg.encoder_layers > 1:  # third point isolates the encoder scan
        v3 = dc.replace(cfg, n_layers=plen, encoder_layers=2)
        f3, b3, c3 = _measure(arch, shape_name, mesh, v3)
        ne = cfg.encoder_layers
        flops += (ne - 1) * (f3 - f1)
        byts += (ne - 1) * (b3 - b1)
        col += (ne - 1) * (c3["total_bytes"] - c1["total_bytes"])
        cnt += (ne - 1) * (c3["total_count"] - c1["total_count"])
    record["flops_per_device_corrected"] = flops
    record["bytes_per_device_corrected"] = byts
    record["collective_bytes_corrected"] = col
    record["collective_count_corrected"] = cnt


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             save_hlo: bool = False, correct: bool = True) -> dict:
    import jax
    from repro.configs.registry import cell_is_skipped, get_config
    from repro.launch.analysis import analytical_bytes, analytical_flops
    from repro.launch.mesh import make_production_mesh

    record = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
              "status": "ok"}
    skip = cell_is_skipped(arch, shape_name)
    if skip:
        record["status"] = "skipped"
        record["reason"] = skip
        _save(record, out_dir)
        return record

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi_pod"))
    fn, args, shardings, donate, out_sh = build_lowerable(
        arch, shape_name, mesh)
    jit_kwargs = {"in_shardings": shardings}
    if donate is not None:
        jit_kwargs["donate_argnums"] = donate
    if out_sh is not None:
        jit_kwargs["out_shardings"] = out_sh
    with mesh:
        lowered = jax.jit(fn, **jit_kwargs).lower(*args)
        record["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t1, 2)

        mem = compiled.memory_analysis()
        print("memory_analysis:", mem)
        if mem is not None:
            for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                         "temp_size_in_bytes", "alias_size_in_bytes",
                         "generated_code_size_in_bytes"):
                v = getattr(mem, attr, None)
                if v is not None:
                    record[attr] = int(v)

        cost = cost_analysis_dict(compiled)
        print("cost_analysis:", {k: v for k, v in sorted(cost.items())
                                 if "{" not in k})
        record["flops_per_device"] = float(cost.get("flops", 0.0))
        record["bytes_per_device"] = float(cost.get("bytes accessed", 0.0))

        hlo = compiled.as_text()
        record["collectives"] = parse_collectives(hlo)
        if save_hlo:
            os.makedirs(out_dir, exist_ok=True)
            with open(f"{out_dir}/{_name(record)}.hlo", "w") as f:
                f.write(hlo)
    record["n_devices"] = mesh.size

    cfg = get_config(arch)
    fr = analytical_flops(cfg, shape_name)
    record["analytical_flops_global"] = fr.total
    record["analytical_flops_ideal"] = fr.ideal
    record["model_flops_6nd"] = fr.model_flops_6nd
    record["analytical_bytes_global"] = analytical_bytes(cfg, shape_name)
    if correct and mesh_kind == "single_pod":
        scan_corrected(arch, shape_name, mesh, record)
    record["total_s"] = round(time.time() - t0, 2)
    _save(record, out_dir)
    return record


def _name(rec):
    return f"{rec['arch']}__{rec['shape']}__{rec['mesh']}".replace("/", "_")


def _save(rec, out_dir):
    os.makedirs(out_dir, exist_ok=True)
    with open(f"{out_dir}/{_name(rec)}.json", "w") as f:
        json.dump(rec, f, indent=2)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single_pod",
                    choices=["single_pod", "multi_pod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--no-correct", action="store_true",
                    help="skip the scan-undercount correction compiles")
    args = ap.parse_args()

    from repro.configs.registry import ARCHS
    from repro.configs.shapes import SHAPES

    archs = sorted(ARCHS) if args.all or not args.arch else [args.arch]
    shapes = sorted(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = (["single_pod", "multi_pod"] if args.mesh == "both"
              else [args.mesh])

    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                tag = f"{arch} x {shape} x {mesh_kind}"
                print(f"=== dryrun {tag}", flush=True)
                try:
                    rec = run_cell(arch, shape, mesh_kind, args.out,
                                   save_hlo=args.save_hlo,
                                   correct=not args.no_correct)
                    print(f"=== done {tag}: {rec['status']} "
                          f"lower={rec.get('lower_s')}s "
                          f"compile={rec.get('compile_s')}s", flush=True)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    failures.append(tag)
                    _save({"arch": arch, "shape": shape, "mesh": mesh_kind,
                           "status": "error", "error": str(e)}, args.out)
    if failures:
        print("FAILURES:", failures)
        sys.exit(1)
    print("all cells ok")


if __name__ == "__main__":
    main()
