"""Multi-cell (hierarchical) FLOWN: two base stations each run the paper's
full Stackelberg round over their own devices/channels; cell models merge
by transmitted data size — the FL semantics of the multi-pod mesh's `pod`
axis (DESIGN.md §2, repro.fl.hierarchical).

Runs the device-resident scan engine (one fused `lax.scan` over rounds,
cells unrolled in its body — same engine matrix as the single-cell
harness, DESIGN.md §10); pass --engine loop for the host reference.

  PYTHONPATH=src python examples/multi_cell.py [--engine loop]
"""
import argparse

from repro.core import RoundPolicy
from repro.fl import HierSimConfig, run_hierarchical


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--engine", choices=("scan", "loop"), default="scan")
    engine = ap.parse_args().engine
    for name, ds in [("proposed", "alg3"), ("random", "random")]:
        out = run_hierarchical(HierSimConfig(
            rounds=30, policy=RoundPolicy(ds=ds), seed=0), engine=engine)
        print(f"2-cell {name:10s} [{engine}]: loss {out['loss'][0]:.3f} -> "
              f"{out['loss'][-1]:.3f}  "
              f"mean round latency {out['latency'].mean():.2f}s "
              f"(max over cells, cells parallel)  "
              f"wall {out['wall_s']:.1f}s")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
