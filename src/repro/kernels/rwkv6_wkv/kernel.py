"""WKV6 recurrence (RWKV-6 "Finch" time-mix) as a Pallas TPU kernel.

    S_t[i,j] = w_t[i] S_{t-1}[i,j] + k_t[i] v_t[j]
    y_t[j]   = sum_i r_t[i] (S_{t-1}[i,j] + u[i] k_t[i] v_t[j])

TPU adaptation: the recurrence is O(hs^2) per head-step and strictly
sequential in time, so the kernel tiles (batch*head) over the parallel grid
axis and streams the time axis in VMEM blocks of `bt` steps; the (hs, hs)
state lives in VMEM scratch and persists across the sequential time-grid
steps. Each time step is an outer-product + reduction on (hs, hs) = (64, 64)
tiles — VPU-friendly, no HBM round-trips for the state (the CUDA reference
keeps state in registers/shared memory; VMEM scratch is the TPU analogue).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wkv6_call"]


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                 y_ref, sout_ref, state, *, bt, nt):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        state[...] = s0_ref[...][0].astype(jnp.float32)

    def step(t, _):
        r = r_ref[0, t].astype(jnp.float32)   # (hs,)
        k = k_ref[0, t].astype(jnp.float32)
        v = v_ref[0, t].astype(jnp.float32)
        w = w_ref[0, t].astype(jnp.float32)
        u = u_ref[0].astype(jnp.float32)
        kv = k[:, None] * v[None, :]          # (hs, hs)
        y = ((state[...] + u[:, None] * kv) * r[:, None]).sum(axis=0)
        y_ref[pl.ds(0, 1), pl.ds(t, 1), :] = y[None, None].astype(y_ref.dtype)
        state[...] = w[:, None] * state[...] + kv
        return 0

    jax.lax.fori_loop(0, bt, step, 0)

    @pl.when(it == nt - 1)
    def _finish():
        # Full-block store: integer-indexed ref writes hit a discharge bug
        # in interpret mode on this jax version.
        sout_ref[...] = state[...][None].astype(sout_ref.dtype)


def wkv6_call(r, k, v, w, u, s0, *, bt: int = 128, interpret: bool = False):
    """r,k,v,w: (BH, T, hs); u: (BH, hs); s0: (BH, hs, hs).
    Returns (y (BH, T, hs), s_final (BH, hs, hs))."""
    bh, t, hs = r.shape
    bt = min(bt, t)
    assert t % bt == 0, (t, bt)
    nt = t // bt

    kernel = functools.partial(_wkv6_kernel, bt=bt, nt=nt)
    return pl.pallas_call(
        kernel,
        grid=(bh, nt),
        in_specs=[
            pl.BlockSpec((1, bt, hs), lambda b, i: (b, i, 0)),  # r
            pl.BlockSpec((1, bt, hs), lambda b, i: (b, i, 0)),  # k
            pl.BlockSpec((1, bt, hs), lambda b, i: (b, i, 0)),  # v
            pl.BlockSpec((1, bt, hs), lambda b, i: (b, i, 0)),  # w
            pl.BlockSpec((1, hs), lambda b, i: (b, 0)),         # u
            pl.BlockSpec((1, hs, hs), lambda b, i: (b, 0, 0)),  # s0
        ],
        out_specs=[
            pl.BlockSpec((1, bt, hs), lambda b, i: (b, i, 0)),  # y
            pl.BlockSpec((1, hs, hs), lambda b, i: (b, 0, 0)),  # s_final
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, hs), r.dtype),
            jax.ShapeDtypeStruct((bh, hs, hs), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hs, hs), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u, s0)
