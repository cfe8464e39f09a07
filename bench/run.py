"""Run one benchmark cell once on the accelerator this machine holds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, entry path and per-layer metrics are
files under `bench/`, found by the names in `BENCHMARK.json`.  The run
enables the compile cache at its fixed path in the checkout, builds the
world from the seed, warms the cell's own shapes (set-up), measures for
`--seconds`, checks what the timed path produced against the plain
reference in `bench/reference/`, and prints one JSON line last.  With no
TPU, or fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if args.seed < 0 or args.seconds <= 0:
        print("--seed must be >= 0 and --seconds > 0", file=sys.stderr)
        return 2
    # The cache key holds the directory, so it is fixed in the checkout.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import harness
    try:
        files = harness.cell_files(args.workload)
        run = harness.Run(args, files, T_START)
        device = harness.device_info(run.cell["chips"])
        import jax
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        path = harness.load_module(
            BENCH / "paths" / f"{run.traffic['path']}.py")
        line = path.run(run, device)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
