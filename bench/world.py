"""The seeded world of a cell, as its traffic file asks for it.

`stratified` data sizes: the paper's imbalanced-IID partition draws
c_n ~ U[1, 10] per device (`data.fl_datasets.partition_imbalanced_iid`);
here every seed gets the same multiset of c_n, the N mid-quantiles of
U[1, 10], dealt to the devices in the order of the N uniforms the program
draws.  So every seed holds the same data sizes in another order, the
client buffers keep one padded shape, and every seed runs the programs
that set-up compiled.  The rest of the partition is the program's rule
(floor, rounding fix, one shuffled split), and the generator's stream is
consumed exactly as the program consumes it.
"""
from __future__ import annotations

import numpy as np


def stratified_partition(rng: np.random.Generator, n_samples: int,
                         n_devices: int):
    from repro.data.fl_datasets import FLPartition
    drawn = rng.uniform(1.0, 10.0, size=n_devices)
    c = np.empty(n_devices)
    c[np.argsort(drawn, kind="stable")] = 1.0 + 9.0 * (
        np.arange(n_devices) + 0.5) / n_devices
    counts = np.maximum(1, np.floor(c / c.sum() * n_samples).astype(np.int64))
    while counts.sum() > n_samples:
        counts[np.argmax(counts)] -= 1
    perm = rng.permutation(n_samples)
    splits = np.cumsum(counts)[:-1]
    idx = tuple(np.array(a) for a in np.split(perm[: counts.sum()], splits))
    return FLPartition(indices=idx, beta=counts)


def install(run):
    """Make the program draw the traffic file's data sizes."""
    if run.traffic.get("data_sizes", "paper") == "stratified":
        import repro.fl.sim as sim
        run.patch(sim, "partition_imbalanced_iid", stratified_partition)


def world_seed(seed: int, *salt: int) -> int:
    """A 31-bit world seed for the program, drawn from the run's seed."""
    ss = np.random.SeedSequence([int(seed), *map(int, salt)])
    return int(ss.generate_state(1, np.uint32)[0] & 0x7FFFFFFF)


def sim_config(config: dict, traffic: dict, seed: int, **over):
    """The program's `SimConfig` for one world of this cell."""
    from repro.core import RoundPolicy
    from repro.fl import SimConfig
    t1 = config["table1"]
    kw = dict(dataset=config["dataset"], n_devices=traffic["n_devices"],
              n_subchannels=traffic["n_subchannels"],
              rounds=traffic.get("rounds", 1), seed=seed,
              n_samples=config["n_samples"],
              local_steps=config["local_steps"],
              radius_m=config["wireless"]["radius_m"],
              pt_dbm=config["wireless"]["pt_dbm"],
              e_max_j=t1["e_max_j"], lr=t1["lr"], batch=t1["batch"],
              optimizer=t1["optimizer"],
              scenario=traffic["scenario"], aggregation=traffic["aggregation"],
              eval_every=traffic.get("eval_every", 1),
              policy=RoundPolicy(**traffic.get("policy", {})))
    kw.update(over)
    return SimConfig(**kw)
