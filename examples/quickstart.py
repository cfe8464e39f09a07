"""Quickstart: the paper in ~60 seconds.

1. Solve ONE Stackelberg round: MO-RA (Alg. 1) -> M-SA (Alg. 2) -> AoU
   device selection (Alg. 3), and print the round plan.
2. Run a short wireless-FL simulation comparing the proposed scheme against
   random device selection on synthetic MNIST.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import (
    RoundPolicy,
    WirelessConfig,
    init_aou,
    plan_round,
    sample_channel_gains,
    sample_topology,
)
from repro.fl import SimConfig, run_simulation


def one_round():
    print("=" * 60)
    print("ONE STACKELBERG ROUND  (N=20 devices, K=4 sub-channels)")
    print("=" * 60)
    cfg = WirelessConfig()
    rng = np.random.default_rng(0)
    topo = sample_topology(rng, cfg)
    h2 = sample_channel_gains(rng, cfg, topo)
    beta = rng.integers(10, 50, cfg.n_devices).astype(float)
    aou = init_aou(cfg.n_devices)

    plan = plan_round(aou, beta, h2, cfg, rng, policy=RoundPolicy())
    print(f"Prop-1 feasible (device,channel) pairs: "
          f"{plan.feasible.sum()}/{plan.feasible.size}")
    print(f"selected devices : {np.where(plan.selected)[0].tolist()}")
    print(f"transmitting     : {np.where(plan.transmitted)[0].tolist()}")
    for n in np.where(plan.transmitted)[0]:
        print(f"  device {n:2d}: sub-channel {plan.channel_of[n]}, "
              f"tau*={plan.tau[n]:.3f} p*={plan.p[n]:.3f} "
              f"T={plan.time_per_device[n]:.2f}s "
              f"E={plan.energy_per_device[n]*1e3:.1f}mJ "
              f"(budget {cfg.e_max_j*1e3:.0f}mJ)")
    print(f"round latency (eq. 9): {plan.latency_s:.2f}s")


def short_sim():
    print()
    print("=" * 60)
    print("30-ROUND FL SIMULATION  (synthetic MNIST, real training)")
    print("=" * 60)
    for name, ds in [("proposed (Alg.3 + MO-RA + M-SA)", "alg3"),
                     ("random device selection", "random")]:
        h = run_simulation(SimConfig(dataset="mnist", rounds=30,
                                     policy=RoundPolicy(ds=ds),
                                     n_samples=400, eval_every=10))
        print(f"{name:36s} loss {h.global_loss[0]:.3f} -> {h.global_loss[-1]:.3f}"
              f"  acc {h.accuracy[-1]:.3f}  conv-time {h.cum_time_s[-1]:.0f}s")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    one_round()
    short_sim()
