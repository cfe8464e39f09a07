"""Client-side local training (paper eq. 33 generalized to the Table-I
settings: minibatch local steps with SGD/Adam).

All selected clients train *in parallel* via vmap over a fixed number of
slots K (the sub-channel count), so the per-round computation jits once.
Empty slots (no transmitting device) carry weight 0 and are discarded at
aggregation.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from .. import tracing
from ..train.optimizer import Optimizer, apply_updates

__all__ = ["make_local_trainer"]


def make_local_trainer(
    loss_fn: Callable[[Any, jax.Array, jax.Array], jax.Array],
    opt: Optimizer,
    *,
    batch_size: int,
    local_steps: int,
    loss_per_example: Callable[[Any, jax.Array, jax.Array], jax.Array] | None = None,
    jit: bool = True,
):
    """Build a jitted vmapped local trainer.

    Returns fn(params, x_slots, y_slots, mask_slots, keys) -> stacked params
    with shapes x_slots (K, Bmax, ...), mask_slots (K, Bmax), keys (K, 2).

    loss_per_example, when provided, computes the whole minibatch in ONE
    model application (essential for conv models: the vmap fallback runs
    batch-1 forwards, ~50x slower on CPU).

    jit=False returns the raw traceable function instead of a `jax.jit`
    wrapper — the scan engine (fl.sim) embeds it inside its fused round
    loop.  One slot's training is a `jax.jit` of its own either way: the
    K slots trace it once, and a trainer kept across calls
    (`fl.sim._group_trainer_and_policies`) is not traced again by a
    round loop that is.
    """

    def masked_loss(params, x, y, m):
        # Per-sample loss weighted by the padding mask.
        if loss_per_example is not None:
            per = loss_per_example(params, x, y)
        else:
            per = jax.vmap(lambda xi, yi: loss_fn(params, xi[None], yi[None]))(x, y)
        return (per * m).sum() / jnp.maximum(m.sum(), 1.0)

    @jax.jit
    def one_client(params, x, y, mask, key):
        tracing.count("engine.subprogram_traces")
        # Local steps UNROLLED (local_steps is small + static): XLA-CPU
        # executes a lax.scan of this body ~30x slower than the unrolled
        # form (measured; conv grads inside scan hit a slow path).
        opt_state = opt.init(params)
        # Minibatches sample only the device's REAL rows (mask prefix), via
        # floor(u * n_valid): the draw is independent of how far the slot
        # buffer happens to be padded, so a simulation's trajectory cannot
        # depend on which other sims share its (group-padded) vmap batch.
        n_valid = jnp.maximum(mask.sum(), 1.0)
        for k in jax.random.split(key, local_steps):
            u = jax.random.uniform(k, (batch_size,))
            idx = (u * n_valid).astype(jnp.int32)    # u < 1 => idx < n_valid
            g = jax.grad(masked_loss)(params, x[idx], y[idx], mask[idx])
            upd, opt_state = opt.update(g, opt_state, params)
            params = apply_updates(params, upd)
        return params

    def train_slots(params, x_slots, y_slots, mask_slots, keys):
        # Unrolled over the K slots, NOT vmap/lax.map: XLA-CPU executes both
        # vmapped and scanned conv gradients ~30-400x slower than the plain
        # unrolled form (measured); K = n_subchannels is small and static.
        # On TPU flip this to vmap for true client parallelism.
        outs = [
            one_client(params, x_slots[i], y_slots[i], mask_slots[i], keys[i])
            for i in range(x_slots.shape[0])
        ]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)

    return jax.jit(train_slots) if jit else train_slots
