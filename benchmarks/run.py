"""Figure benchmarks — one module per paper figure.

Prints ``name,us_per_call,derived`` CSV (benchmarks/common.emit).

  PYTHONPATH=src python -m benchmarks.run                   # everything
  PYTHONPATH=src python -m benchmarks.run fig4 fig9         # a subset
  PYTHONPATH=src python -m benchmarks.run --only fig4,fig9  # same, flag form

``--only`` and the positional names both accept comma-separated lists and
compose; unknown names fail fast with the available set.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    from benchmarks import (cfd_dryrun, cfd_modes, fig4_lsp_vs_alpha,
                            fig5_host_time, fig6_phi_ratio, fig7_full_mesh,
                            fig7_strong_scaling, fig8_speedup,
                            fig9_gpu_aware, fig10_adaptive,
                            fig11_fused_krylov, fig12_step_program,
                            fig13_engine_throughput, fig14_cases,
                            fig15_supervision, hillclimb)

    suites = {
        "fig4": fig4_lsp_vs_alpha.run,
        "fig5": fig5_host_time.run,
        "fig6": fig6_phi_ratio.run,
        "fig7": fig7_strong_scaling.run,
        "fig7fm": fig7_full_mesh.run,
        "fig8": fig8_speedup.run,
        "fig9": fig9_gpu_aware.run,
        "fig10": fig10_adaptive.main,
        "fig11": fig11_fused_krylov.run,
        "fig12": fig12_step_program.run,
        "fig13": fig13_engine_throughput.run,
        "fig14": fig14_cases.run,
        "fig15": fig15_supervision.run,
        "cfd_dryrun": cfd_dryrun.run,
        "cfd_modes": cfd_modes.run,
        "hillclimb": hillclimb.run,
    }
    heavy = {"cfd_dryrun", "cfd_modes", "hillclimb", "fig7fm", "fig10",
             "fig11", "fig12", "fig13", "fig14", "fig15"}

    ap = argparse.ArgumentParser()
    ap.add_argument("names", nargs="*",
                    help="figure names (comma-separated lists accepted)")
    ap.add_argument("--only", default=None,
                    help="comma-separated figure names to run")
    args = ap.parse_args()

    picked: list[str] = []
    for token in args.names + ([args.only] if args.only else []):
        picked.extend(name for name in token.split(",") if name)
    unknown = [name for name in picked if name not in suites]
    if unknown:
        sys.exit(f"unknown figure(s) {unknown}; available: "
                 f"{', '.join(sorted(suites))}")
    picked = picked or [k for k in suites if k not in heavy]

    print("name,us_per_call,derived")
    failures = []
    for name in picked:
        try:
            suites[name]()
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            failures.append(name)
            print(f"{name}_SUITE_ERROR,0,{e}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
