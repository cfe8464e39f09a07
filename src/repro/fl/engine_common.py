"""Traced-body pieces shared by the scan and async engines.

The async engine's bit-exact sync-limit contract (DESIGN.md §12) holds
only while both engines trace the SAME float ops for the leader step,
the client-training PRNG discipline, and the eval path — so those pieces
live here once, imported by `fl.sim._build_scan_runner` and
`fl.async_loop.build_async_runner`, instead of being mirrored by hand.
Everything here is pure tracing scaffolding over the `data` dict contract
of `fl.sim._scan_inputs`; no dispatch or history logic.

The leader step is called through `jax.jit`, so a runner built afresh for
every call re-traces only its round loop's glue: each leader variant is
traced once per process per (static arguments, shapes).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from .. import tracing
from ..core import leader_round

__all__ = ["make_leader_branches", "run_leader", "train_clients",
           "make_eval_fn", "make_xs"]


def make_leader_branches(policies: Sequence[tuple[str, str]], data, *,
                         k: int, n: int, n_clusters: int,
                         max_rounds: int = 200):
    """One `leader_round` closure per distinct (ds, sa) policy variant.

    Each branch takes ``(age, feasible, x)`` — the feasibility mask is an
    explicit operand so the async engine can knock busy devices out of
    Prop-1 (the scan engine passes ``x["feas"]`` unchanged).
    """
    def leader_branch(ds, sa):
        def branch(ops):
            age, feas, x = ops
            return _jitted(leader_round)(
                age, data["beta"], x["gamma"], feas,
                x["sel_perm"], x["assign_perm"], x["t"],
                data["clusters"], data["fixed_ids"],
                ds=ds, sa=sa, k=k, n=n, n_clusters=n_clusters,
                max_rounds=max_rounds)
        return branch

    return [leader_branch(ds, sa) for ds, sa in policies]


@functools.cache
def _jitted(fn):
    """`fn` (a `leader_round`) under `jax.jit` with its policy and shape
    arguments static.  Memoised on the function object, which the branches
    resolve by this module's global name at each call, so one patched in
    its place is jitted on its own.  Its Python body runs only when JAX
    misses its trace cache: that is what `engine.subprogram_traces`
    counts."""
    @functools.wraps(fn)
    def traced(*args, **kw):
        tracing.count("engine.subprogram_traces")
        return fn(*args, **kw)
    return jax.jit(traced, static_argnames=(
        "ds", "sa", "k", "n", "n_clusters", "max_rounds"))


def run_leader(branches, policy_idx, age, feasible, x):
    """Dispatch one leader step: direct call for single-policy groups,
    `lax.switch` on the cell's policy index otherwise (DESIGN.md §10)."""
    if len(branches) == 1:
        return branches[0]((age, feasible, x))
    return jax.lax.switch(policy_idx, branches, (age, feasible, x))


def train_clients(trainer, data, k: int, params, key, tx_ids):
    """The engines' shared training step and PRNG discipline: exactly one
    key split per training event, then K per-slot keys — both engines
    MUST consume the stream identically or the differential contracts
    break.  Returns (client_params, advanced_key)."""
    key, k_round = jax.random.split(key)
    keys = jax.random.split(k_round, k)
    cp = trainer(params, data["x_all"][tx_ids], data["y_all"][tx_ids],
                 data["m_all"][tx_ids], keys)
    return cp, key


def make_eval_fn(model, data, track_gradnorm: bool):
    """The eval-round branch: (loss, accuracy, grad-norm^2-if-tracked)."""
    f0 = jnp.float32(0.0)

    def gnorm_fn(p):
        return sum(
            jnp.sum(jnp.square(g))
            for g in jax.tree_util.tree_leaves(
                jax.grad(model.loss)(p, data["x_full"], data["y_full"])))

    def ev(p):
        gn = gnorm_fn(p) if track_gradnorm else f0
        return (model.loss(p, data["x_full"], data["y_full"]),
                model.accuracy(p, data["x_full"], data["y_full"]),
                jnp.float32(gn))

    return ev


def make_xs(data, rounds: int, eval_mask) -> dict:
    """The per-round scan xs both engines consume: Γ slices, injected
    permutations, the eval mask, and the round index."""
    return dict(gamma=data["gamma"], feas=data["feas"],
                energy=data["energy"], sel_perm=data["sel_perms"],
                assign_perm=data["assign_perms"],
                eval_mask=jnp.asarray(eval_mask),
                t=jnp.arange(rounds, dtype=jnp.int32))
