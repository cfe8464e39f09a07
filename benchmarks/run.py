"""Benchmark entrypoint: one table per paper figure + Prop-3 + kernels +
control plane + roofline. Prints name,...,derived CSV blocks
(``#table,<name>`` headers).

  PYTHONPATH=src python -m benchmarks.run            # reduced scale
  PYTHONPATH=src python -m benchmarks.run --full     # paper scale
  PYTHONPATH=src python -m benchmarks.run --only fig3_global_loss
  PYTHONPATH=src python -m benchmarks.run --json     # + machine-readable
                                                     #   BENCH_control_plane.json
"""
from __future__ import annotations

import sys
import time
import traceback

from repro.compile_cache import enable_compile_cache

from . import (
    control_plane,
    fig3_global_loss,
    fig4_ablation,
    fig5_num_devices,
    fig6_radius,
    fig7_subchannels,
    fig8_energy,
    fig9_power,
    kernels_micro,
    prop3_bound,
    roofline,
)

CONTROL_PLANE_JSON = "BENCH_control_plane.json"


def _failed_gates(record, prefix=""):
    """Walk a benchmark record for `meets_target`/`meets_rel` False flags.

    The control-plane record marks each acceptance row with a boolean gate
    (horizon speedup, sweep grid, fused polyblock speedup/agreement/roofline
    floor).  Any False is a perf regression the bench must surface as a
    nonzero exit, not just a table row (ISSUE: "fail the bench if the fused
    solve regresses below target").
    """
    bad = []
    if isinstance(record, dict):
        for k, v in record.items():
            if k in ("meets_target", "meets_rel") and v is False:
                bad.append(f"{prefix}{k}")
            else:
                bad.extend(_failed_gates(v, f"{prefix}{k}."))
    return bad

ALL = {
    "fig3_global_loss": fig3_global_loss.run,
    "fig4_ablation": fig4_ablation.run,
    "fig5_num_devices": fig5_num_devices.run,
    "fig6_radius": fig6_radius.run,
    "fig7_subchannels": fig7_subchannels.run,
    "fig8_energy": fig8_energy.run,
    "fig9_power": fig9_power.run,
    "prop3_bound": prop3_bound.run,
    "kernels_micro": kernels_micro.run,
    "control_plane": control_plane.run,
    "roofline": roofline.run,
}


def main() -> None:
    enable_compile_cache()
    only = None
    if "--only" in sys.argv:
        only = sys.argv[sys.argv.index("--only") + 1]
    runners = dict(ALL)
    if "--json" in sys.argv:  # bind options at registration, not dispatch
        runners["control_plane"] = lambda: control_plane.run(
            json_path=CONTROL_PLANE_JSON)
    t0 = time.time()
    failed = []
    for name, fn in runners.items():
        if only and name != only:
            continue
        t = time.time()
        try:
            record = fn()
        except Exception as e:  # noqa: BLE001 - report, run the rest, exit 1
            traceback.print_exc()
            print(f"#table,{name}\nERROR,{type(e).__name__}: {e}")
            failed.append(f"{name}: raised {type(e).__name__}")
        else:
            failed += [f"{name}: {g}" for g in _failed_gates(record)]
        print(f"# {name} took {time.time()-t:.1f}s\n")
    print(f"# total {time.time()-t0:.1f}s")
    if failed:
        print("# FAILURES:\n" + "\n".join(f"#   {g}" for g in failed))
        sys.exit(1)


if __name__ == "__main__":
    main()
