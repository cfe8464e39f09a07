"""Mixture-of-Experts FFN: dropping top-k routing with sort-based capacity
dispatch, TPU expert parallelism via shard_map.

Why this formulation (DESIGN.md hardware-adaptation):
  * GShard-style one-hot dispatch einsums inflate HLO FLOPs by the dispatch
    tensor (T x E x C) — catastrophic for both memory and the roofline's
    "useful FLOPs" ratio.  Instead we sort token-copies by expert id and
    scatter them into a fixed-capacity buffer (E_local, C, d): the dispatch
    is pure data movement (gather/scatter), and the expert matmuls are dense
    (E_local, C, d) x (E_local, d, ff) einsums that map straight onto the MXU.
  * Expert parallelism: experts are sharded over the `model` mesh axis;
    activations stay sharded over the data axes and replicated over `model`.
    Each model shard dispatches only to ITS local experts and contributes a
    partial output; one psum over `model` combines (this trades the classic
    all-to-all for an all-reduce of (T, d) — on a 16-way model axis this is
    the cheaper collective whenever top_k * capacity > d_model/16, which
    holds for every assigned MoE config).
  * Experts are zero-padded to a multiple of the expert-parallel degree
    (granite's 40 experts -> 48 on a 16-way axis); padded experts receive no
    router probability mass.

Capacity: C = ceil(T * top_k / E * capacity_factor); overflowing tokens are
dropped (their copies contribute 0), standard for capacity-based TPU MoE.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig
from .layers import DTYPE, dense_init, swiglu, swiglu_init

__all__ = ["ShardCtx", "moe_init", "moe_apply", "pad_experts", "CAPACITY_FACTOR"]

CAPACITY_FACTOR = 1.25


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Static sharding context threaded through the model assembly.

    mesh=None -> single-device math (smoke tests / FL simulation).
    attn_shard: "auto" leaves attention partitioning to GSPMD (baseline);
    "explicit" wraps full-sequence attention in shard_map (head-parallel
    when kv-heads divide the model axis, sequence-parallel otherwise) —
    the §Perf optimization that removes GSPMD's per-chunk score all-reduce.
    """

    mesh: Any = None
    dp_axes: tuple = ("data",)      # activation batch axes
    ep_axis: str = "model"          # expert-parallel axis
    attn_shard: str = "auto"        # "auto" | "explicit"

    @property
    def ep_size(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.ep_axis]


def pad_experts(n_experts: int, ep_size: int) -> int:
    return ((n_experts + ep_size - 1) // ep_size) * ep_size


def moe_init(key, cfg: ArchConfig, *, ep_size: int = 1):
    e_pad = pad_experts(cfg.n_experts, ep_size)
    ff = cfg.ffn_expert
    ks = jax.random.split(key, 5)
    scale = (2.0 / (cfg.d_model + ff)) ** 0.5
    p = {
        "router": dense_init(ks[0], cfg.d_model, cfg.n_experts, scale=0.02),
        "gate": (jax.random.normal(ks[1], (e_pad, cfg.d_model, ff)) * scale).astype(DTYPE),
        "up": (jax.random.normal(ks[2], (e_pad, cfg.d_model, ff)) * scale).astype(DTYPE),
        "down": (jax.random.normal(ks[3], (e_pad, ff, cfg.d_model)) * scale).astype(DTYPE),
    }
    if cfg.n_shared_experts > 0:
        p["shared"] = swiglu_init(ks[4], cfg.d_model, ff * cfg.n_shared_experts)
    return p


def _capacity(n_tokens: int, cfg: ArchConfig) -> int:
    """Expert capacity. Small token counts (decode steps, smoke tests) get
    C = T * top_k, i.e. *dropless* exact routing; at scale the standard
    capacity-factor bound applies and overflow tokens are dropped."""
    if n_tokens * cfg.top_k <= 256:
        return n_tokens * cfg.top_k
    c = int(n_tokens * cfg.top_k * CAPACITY_FACTOR / cfg.n_experts) + 1
    return max(c, cfg.top_k)


def _local_moe(x2d, router_w, gate, up, down, cfg: ArchConfig, capacity: int, e_offset):
    """Dispatch T tokens to the n_local experts held by this shard.

    x2d (T, d); gate/up/down (E_local, d|ff, ff|d); e_offset int32 global id
    of this shard's first expert.  Returns (y (T, d), aux_loss ()).
    """
    t, d = x2d.shape
    n_local = gate.shape[0]
    k = cfg.top_k

    logits = (x2d.astype(jnp.float32) @ router_w.astype(jnp.float32))  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                             # (T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # Load-balance aux (Switch-style): E * sum_e f_e * P_e.
    frac = jnp.zeros((cfg.n_experts,), jnp.float32).at[top_e.reshape(-1)].add(1.0)
    frac = frac / (t * k)
    aux = cfg.n_experts * jnp.sum(frac * probs.mean(0))

    # ---- flatten the T*k token copies and keep those routed locally. -----
    e_flat = top_e.reshape(-1) - e_offset                               # (T*k,)
    w_flat = top_p.reshape(-1)
    tok_flat = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    valid = (e_flat >= 0) & (e_flat < n_local)
    key = jnp.where(valid, e_flat, n_local)                             # invalid -> end
    order = jnp.argsort(key, stable=True)
    e_sorted = key[order]
    tok_sorted = tok_flat[order]
    w_sorted = w_flat[order]

    counts = jnp.bincount(key, length=n_local + 1)[:n_local]
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)])
    rank = jnp.arange(t * k) - starts[jnp.minimum(e_sorted, n_local)]
    keep = (e_sorted < n_local) & (rank < capacity)
    slot = jnp.where(keep, e_sorted * capacity + rank, n_local * capacity)

    # ---- scatter into the (E_local * C) buffer, run the experts. ---------
    gathered = x2d[tok_sorted] * keep[:, None].astype(x2d.dtype)
    buf = jnp.zeros((n_local * capacity + 1, d), x2d.dtype).at[slot].set(gathered)
    buf = buf[:-1].reshape(n_local, capacity, d)

    h = jnp.einsum("ecd,edf->ecf", buf, gate)
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buf, up)
    out = jnp.einsum("ecf,efd->ecd", h, down)                           # (E_l, C, d)

    # ---- combine back: gather by slot, weight, scatter-add by token. ----
    out_flat = jnp.concatenate([out.reshape(n_local * capacity, d),
                                jnp.zeros((1, d), out.dtype)])
    y_sorted = out_flat[slot] * (w_sorted * keep)[:, None].astype(out.dtype)
    y = jnp.zeros((t, d), out.dtype).at[tok_sorted].add(y_sorted)
    return y, aux


def moe_apply(p, cfg: ArchConfig, x, ctx: ShardCtx):
    """x: (B, S, d) -> (y, aux_loss).  Shared experts (deepseek) are a plain
    dense SwiGLU added to the routed output."""
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)

    if ctx.mesh is None:
        cap = _capacity(b * s, cfg)
        y2d, aux = _local_moe(
            x2d, p["router"]["w"], p["gate"], p["up"], p["down"], cfg, cap,
            jnp.zeros((), jnp.int32),
        )
    else:
        ep = ctx.ep_size
        n_local = p["gate"].shape[0] // ep
        # Per-shard token count: batch is sharded over the data axes when it
        # divides; otherwise (e.g. long_500k's single decode token) tokens
        # stay replicated and only experts are sharded.
        dp = 1
        for a in ctx.dp_axes:
            dp *= ctx.mesh.shape[a]
        token_sharded = (b * s) % dp == 0
        cap = _capacity((b * s) // dp if token_sharded else b * s, cfg)
        tok_spec = P(ctx.dp_axes, None) if token_sharded else P(None, None)

        def shard_fn(x_l, rw, g_l, u_l, d_l):
            e_off = jax.lax.axis_index(ctx.ep_axis) * n_local
            y_l, aux_l = _local_moe(x_l, rw, g_l, u_l, d_l, cfg, cap, e_off)
            y_l = jax.lax.psum(y_l, ctx.ep_axis)       # combine expert shards
            aux_l = jax.lax.pmean(aux_l, ctx.ep_axis)
            return y_l, aux_l

        y2d, aux = jax.shard_map(
            shard_fn,
            mesh=ctx.mesh,
            in_specs=(
                tok_spec,                              # tokens
                P(None, None),                         # router: replicated
                P(ctx.ep_axis, None, None),            # experts: EP-sharded
                P(ctx.ep_axis, None, None),
                P(ctx.ep_axis, None, None),
            ),
            out_specs=(tok_spec, P()),
            check_vma=False,
        )(x2d, p["router"]["w"], p["gate"], p["up"], p["down"])
        # Name the combined output so the remat policy can SAVE it: without
        # this, rematerialization re-executes the psum in the backward pass,
        # doubling the MoE collective volume (EXPERIMENTS §Perf iteration 2).
        y2d = jax.ad_checkpoint.checkpoint_name(y2d, "moe_out")

    y = y2d.reshape(b, s, d)
    if "shared" in p:
        y = y + swiglu(p["shared"], x)
    return y, aux
