"""End-to-end LM training driver at ~100M parameters: a scaled-down
qwen2-style dense config trained for a few hundred steps on the synthetic
token pipeline, with FL cohort weighting driven by the Stackelberg planner
(the paper's technique as a first-class train_step feature) and periodic
checkpoints.

NOTE: ~100M params on a CPU container is slow (~seconds/step); the default
runs 100 steps with seq 256. On a real TPU mesh the same script scales via
repro.launch (pjit shardings come from repro.sharding.partition).

  PYTHONPATH=src python examples/train_100m.py --steps 100
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import get_config
from repro.data.pipeline import synthetic_lm_stream
from repro.launch.train import fl_round_weights
from repro.core import RoundPolicy, WirelessConfig, init_aou, sample_topology
from repro.models import init_params, param_count
from repro.train.optimizer import make_optimizer
from repro.train.train_step import make_train_step


def make_100m_config():
    """~100M-param dense decoder in the qwen2 family."""
    base = get_config("qwen2-7b")
    return dataclasses.replace(
        base, name="qwen2-100m", n_layers=8, d_model=640, n_heads=10,
        n_kv_heads=2, d_ff=2560, vocab=32768, sliding_window=0,
        long_context="", optimizer="adamw",
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--out", default="results/ckpt_100m.npz")
    a = ap.parse_args()

    cfg = make_100m_config()
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    print(f"{cfg.name}: {param_count(params)/1e6:.1f}M params")

    opt = make_optimizer("adamw", a.lr)
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(cfg, opt, remat=False))
    stream = synthetic_lm_stream(0, a.batch, a.seq, cfg.vocab)

    # FL cohort weighting from the Stackelberg planner (8 cohorts).
    rng = np.random.default_rng(0)
    wcfg = WirelessConfig(n_devices=8, n_subchannels=4)
    fl_state = {"topo": sample_topology(rng, wcfg), "aou": init_aou(8)}
    beta = rng.integers(10, 50, 8).astype(np.float64)
    policy = RoundPolicy()

    t0 = time.time()
    for step in range(a.steps):
        b = next(stream)
        w, plan, lat = fl_round_weights(fl_state, beta, wcfg, rng, policy)
        row_w = w[np.arange(a.batch) % 8]
        if row_w.sum() == 0:
            row_w = np.ones(a.batch)
        batch = {
            "tokens": jnp.asarray(b["tokens"]),
            "labels": jnp.asarray(b["labels"]),
            "fl_weights": jnp.asarray(row_w, jnp.float32),
        }
        params, opt_state, m = step_fn(params, opt_state, batch)
        if step % 10 == 0 or step == a.steps - 1:
            print(f"step {step:4d}  loss {float(m['loss']):.4f}  "
                  f"gnorm {float(m['grad_norm']):.2f}  "
                  f"round_latency {lat:.2f}s  "
                  f"({(time.time()-t0)/(step+1):.2f}s/step)")
        if a.ckpt_every and (step + 1) % a.ckpt_every == 0:
            save_checkpoint(a.out, params, step=step + 1)
            print(f"  checkpoint -> {a.out}")
    print(f"done in {time.time()-t0:.0f}s")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
