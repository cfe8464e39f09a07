"""Faults planted in the program underneath a tiny benchmark run: each
must turn `correct` false."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def state_unchanged(monkeypatch):
    """Local training returns the global model unchanged."""
    import repro.fl.sim as sim

    def make_local_trainer(loss_fn, opt, **kw):
        def train_slots(params, x_slots, y_slots, mask_slots, keys):
            k = keys.shape[0]
            return jax.tree_util.tree_map(
                lambda p: jnp.broadcast_to(p, (k,) + p.shape), params)
        return train_slots
    monkeypatch.setattr(sim, "make_local_trainer", make_local_trainer)


def half_batch(monkeypatch):
    """Each local step averages the loss over half of its minibatch."""
    import repro.fl.sim as sim
    real = sim.make_local_trainer

    def make_local_trainer(loss_fn, opt, *, batch_size, **kw):
        return real(loss_fn, opt, batch_size=max(1, batch_size // 2), **kw)
    monkeypatch.setattr(sim, "make_local_trainer", make_local_trainer)


def answer_altered(monkeypatch):
    """The leader drops its first transmitting device where it decides."""
    import repro.fl.engine_common as ec
    real = ec.leader_round

    def leader_round(age, *a, **kw):
        out = real(age, *a, **kw)
        tx = out["transmitted"]
        tx = tx.at[jnp.argmax(tx)].set(False)
        return dict(out, transmitted=tx,
                    age_next=jnp.where(tx, 1, age + 1).astype(age.dtype))
    monkeypatch.setattr(ec, "leader_round", leader_round)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "answer_altered": answer_altered}
