"""The reference's round loop, built from `leader` and `train`: the
synchronous FedAvg round (eq. 34 after an eq.-9 barrier).  It takes the
world as the program's seeded generator drew it (client data, Γ trace,
permutations) and returns every decision and every evaluation it makes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import leader, train


def _padded(ids: np.ndarray, k: int) -> np.ndarray:
    out = np.zeros(k, np.int64)
    out[:ids.size] = ids
    return out


def replay_sync(inp: dict, data: dict, *, ds: str, k: int,
                trainer: train.Trainer, params, key, eval_rounds):
    """Decisions, eval losses and final parameters of one synchronous
    simulation."""
    tr = leader.sync_traces(inp, ds, k)
    losses = {}
    for t in range(tr["transmitted"].shape[0]):
        ids = np.flatnonzero(tr["transmitted"][t])
        if ids.size:
            cp, key = trainer.train(params, data, _padded(ids, k), key)
            w = np.zeros(k, np.float32)
            w[:ids.size] = inp["beta"][ids]
            params = train.weighted_mean(cp, w)
        if t in eval_rounds:
            losses[t] = trainer.evaluate(params, data["x_full"],
                                         data["y_full"])[0]
    return tr, losses, params

