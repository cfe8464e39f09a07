"""Plain NumPy reference for the leader plane: AoU priorities (eq. 43),
Algorithm 3 selection, the benchmark selection schemes, Algorithm 2 swap
matching, and the two server disciplines that decide when an upload
counts: the synchronous round barrier and the buffered asynchronous
commit.

Randomness is an input: the per-round device and channel permutations are
the ones the program drew from the seed, so a reference and a program
that agree on the semantics agree on every decision bit for bit.  This
module imports nothing of the program.
"""
from __future__ import annotations

import math

import numpy as np

U_MAX = 1e30


def swap_matching(gamma_u: np.ndarray, initial: np.ndarray,
                  max_rounds: int = 200) -> np.ndarray:
    """Algorithm 2: execute the first swap-blocking pair (Definition 2) at
    or after a row-major cursor until a full proposal round finds none.
    gamma_u is (K, n) utilities with U_MAX at infeasible pairs."""
    n = gamma_u.shape[1]
    assign = np.asarray(initial, np.int64).copy()
    dev = np.arange(n)
    nn = n * n
    cursor, swapped, rounds = 0, False, 0
    while rounds < max_rounds:
        u = gamma_u[assign, dev]
        a = gamma_u[assign]
        block = ((a.T <= u[:, None]) & (a <= u[None, :])
                 & ((a.T < u[:, None]) | (a < u[None, :])))
        np.fill_diagonal(block, False)
        ahead = np.flatnonzero(block.ravel()[cursor:])
        if ahead.size:
            q = cursor + int(ahead[0])
            i, j = divmod(q, n)
            assign[i], assign[j] = assign[j], assign[i]
            swapped = True
            cursor = q + 1
            if cursor < nn:
                continue
        rounds += 1
        if not swapped:
            break
        cursor, swapped = 0, False
    return assign


def leader_round(age, beta, gamma, feas, sel_perm, assign_perm, t,
                 clusters, fixed_ids, *, ds: str, k: int):
    """One leader step with Algorithm-2 matching.  Returns (transmitted,
    channel_of): the devices whose matched channel is Proposition-1
    feasible, and that channel (-1 elsewhere)."""
    n = age.shape[0]
    s = min(k, n)
    gamma_u = np.where(feas, gamma, U_MAX)
    gamma_u = np.where(np.isfinite(gamma_u), gamma_u, U_MAX)
    init = np.asarray(assign_perm[:s], np.int64)

    def match(ids):
        assign = swap_matching(gamma_u[:, ids], init[:len(ids)])
        ok = gamma_u[assign, ids] < U_MAX
        return assign, ok

    if ds == "alg3":
        prio = age.astype(np.float32) * beta.astype(np.float32)
        order = np.argsort(-prio, kind="stable")
        ids = order[:s].copy()
        nxt, it = s, 0
        while True:
            assign, ok = match(ids)
            it += 1
            bad = ~ok
            if (not bad.any()) or nxt >= n or it >= n:
                break
            j = np.cumsum(bad) - 1
            src = nxt + j
            take = bad & (src < n)
            ids = np.where(take, order[np.clip(src, 0, n - 1)], ids)
            nxt += int(take.sum())
    elif ds == "random":
        ids = np.asarray(sel_perm[:s], np.int64)
        assign, ok = match(ids)
    elif ds == "fixed":
        ids = np.asarray(fixed_ids, np.int64)
        assign, ok = match(ids)
    elif ds == "cluster":
        n_clusters = int(math.ceil(n / k))
        ids = np.flatnonzero(clusters == (t % n_clusters))[:s]
        assign, ok = match(ids)
    else:
        raise ValueError(f"no reference for selection scheme {ds!r}")
    tx = np.zeros(n, bool)
    ch = np.full(n, -1, np.int64)
    tx[ids[ok]] = True
    ch[ids[ok]] = assign[ok]
    return tx, ch


def sync_traces(inp: dict, ds: str, k: int):
    """The synchronous engine's decisions over a horizon: per round the
    transmitted set, the post-round ages and the eq.-9 latency."""
    gamma, feas = inp["gamma"], inp["feas"]
    rounds, _, n = gamma.shape
    age = np.ones(n, np.int64)
    out = {"transmitted": [], "age": [], "latency": []}
    for t in range(rounds):
        tx, ch = leader_round(age, inp["beta"], gamma[t], feas[t],
                              inp["sel_perms"][t], inp["assign_perms"][t], t,
                              inp["clusters"], inp["fixed_ids"], ds=ds, k=k)
        t_dev = gamma[t][np.where(tx, ch, 0), np.arange(n)]
        age = np.where(tx, 1, age + 1)
        out["transmitted"].append(tx)
        out["age"].append(age.copy())
        out["latency"].append(np.float32(t_dev[tx].max()) if tx.any()
                              else np.float32(0.0))
    return {key: np.stack(v) for key, v in out.items()}

