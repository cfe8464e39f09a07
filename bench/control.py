"""Read, on the chip and at a cell's own size, the numbers that decide
`correct` for the program and for the control on several seeds, in one
process: the limits in each traffic file are set between the two.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 3

For every seed it runs the cell as `bench/run.py` does (with a short
window and no Γ shadow warm-up, which only keeps compiles out of the
window) and prints one JSON line: the program's gaps to the reference and
the control's gaps to the reference.  With `--fault <name>` it plants
that fault of `bench/tests/faults.py` in the program and reads the
program's gaps alone.  The control is the reference in the
precision below the configuration's: float32 Γ, bfloat16 training.  The
benchmark's own runs never run it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--fault", default=None,
                    help="plant a fault of bench/tests/faults.py in the "
                         "program and read the program only")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import harness
    files = harness.cell_files(args.workload)
    files["traffic"] = dict(files["traffic"], gamma_warm_calls=0)
    try:
        device = harness.device_info(files["cell"]["chips"])
    except harness.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    path = harness.load_module(
        BENCH / "paths" / f"{files['traffic']['path']}.py")
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.Run(SimpleNamespace(workload=args.workload, seed=seed,
                                          seconds=args.seconds, trace=0),
                          files, time.perf_counter())
        run.control = args.fault is None
        if args.fault:
            from bench.tests import faults
            faults.FAULTS[args.fault](SimpleNamespace(setattr=run.patch))
        t0 = time.perf_counter()
        try:
            line = json.loads(path.run(run, device))
        finally:
            run.restore()
        print(json.dumps({
            "seed": seed, "fault": args.fault, "correct": line["correct"],
            "program": {k: c["value"] for k, c in line["checks"].items()},
            "control": run.control_values,
            "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
