"""Kernel micro-benchmarks: wall time of the jnp oracle vs the Pallas kernel.

The kernels follow the platform: compiled to Mosaic on a TPU, interpret
mode elsewhere.  Every row names the backend it ran on; an interpret-mode
timing says how fast the Pallas interpreter is, not the chip (the derived
column reports achieved bytes or flops per call for the roofline)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import WirelessConfig
from repro.kernels.fedavg_agg.ops import fedavg_aggregate
from repro.kernels.fedavg_agg.ref import fedavg_agg_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.polyblock_project.ops import polyblock_project
from repro.kernels.rwkv6_wkv.ops import wkv6_pallas
from repro.kernels.rwkv6_wkv.ref import wkv6_scan_ref

from .common import emit, timed


def run():
    key = jax.random.PRNGKey(0)
    rows = []
    mode = "compiled" if jax.default_backend() == "tpu" else "interpret"

    # flash attention (B=1, S=512, H=4, D=64)
    q = jax.random.normal(key, (1, 512, 4, 64))
    k = jax.random.normal(key, (1, 512, 4, 64))
    v = jax.random.normal(key, (1, 512, 4, 64))
    ref_fn = jax.jit(lambda q, k, v: attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)))
    _, us = timed(lambda: jax.block_until_ready(ref_fn(q, k, v)))
    flops = 4 * 512 * 512 * 4 * 64 / 2
    rows.append(["flash_attention/ref_jnp", round(us, 1), f"{flops/us/1e3:.2f}GF/s"])
    _, us = timed(lambda: jax.block_until_ready(
        flash_attention(q, k, v)))
    rows.append(["flash_attention/pallas", round(us, 1), mode])

    # wkv6 (B=1, T=256, H=4, hs=64)
    r = jax.random.normal(key, (1, 256, 4, 64))
    kk = jax.random.normal(key, (1, 256, 4, 64))
    vv = jax.random.normal(key, (1, 256, 4, 64))
    w = jax.nn.sigmoid(jax.random.normal(key, (1, 256, 4, 64))) * 0.5 + 0.45
    u = 0.1 * jax.random.normal(key, (4, 64))
    s0 = jnp.zeros((1, 4, 64, 64))
    ref_fn = jax.jit(wkv6_scan_ref)
    _, us = timed(lambda: jax.block_until_ready(ref_fn(r, kk, vv, w, u, s0)[0]))
    rows.append(["rwkv6_wkv/ref_jnp", round(us, 1), f"T=256"])
    _, us = timed(lambda: jax.block_until_ready(
        wkv6_pallas(r, kk, vv, w, u, s0)[0]))
    rows.append(["rwkv6_wkv/pallas", round(us, 1), mode])

    # fedavg aggregation (K=4, N=1M)
    x = jax.random.normal(key, (4, 1 << 20))
    wts = jnp.asarray([1.0, 2.0, 0.0, 1.0])
    ref_fn = jax.jit(fedavg_agg_ref)
    _, us = timed(lambda: jax.block_until_ready(ref_fn(x, wts)))
    gbs = x.size * 4 / us / 1e3
    rows.append(["fedavg_agg/ref_jnp", round(us, 1), f"{gbs:.2f}GB/s"])
    _, us = timed(lambda: jax.block_until_ready(
        fedavg_aggregate(x, wts)))
    rows.append(["fedavg_agg/pallas", round(us, 1), mode])

    # polyblock projection (K=4, N sweep): NumPy 60-step bisection vs jitted
    # (jnp mirror + warm-started Newton) vs the Pallas kernel
    wcfg = WirelessConfig()
    rng = np.random.default_rng(0)
    for n in (32, 512, 4096):
        sz = 4 * n
        v = np.stack([rng.uniform(0.05, 1, sz), rng.uniform(0.05, 1, sz)], -1)
        beta = rng.integers(5, 60, sz).astype(float)
        h2 = rng.exponential(size=sz) * 3
        em = np.full(sz, wcfg.e_max_j)
        _, us = timed(lambda: polyblock_project(v, beta, h2, em, wcfg,
                                                backend="ref"))
        rows.append([f"polyblock_project/ref_np/K4xN{n}", round(us, 1),
                     f"{60 * sz} g-evals"])
        with jax.enable_x64(True):  # the solver's production precision
            for be in ("bisect", "newton"):
                fn = jax.jit(lambda v, b, h, e, be=be: polyblock_project(
                    v, b, h, e, wcfg, backend=be))
                args = [jnp.asarray(x) for x in (v, beta, h2, em)]
                _, us = timed(lambda: jax.block_until_ready(fn(*args)))
                rows.append([f"polyblock_project/{be}_jit/K4xN{n}",
                             round(us, 1), f"{sz} pairs, f64"])
        _, us = timed(lambda: jax.block_until_ready(
            polyblock_project(v, beta, h2, em, wcfg, backend="pallas")))
        rows.append([f"polyblock_project/pallas/K4xN{n}", round(us, 1), mode])

    emit("kernels_micro", ["us_per_call", "derived"], rows)
    return rows


if __name__ == "__main__":
    run()
