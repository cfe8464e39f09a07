import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
"""Multi-device EXECUTION demo (not just compile): run real FL-weighted
train steps for a reduced architecture on a (data=4, model=2) mesh of 8
forced host devices, with the paper's Stackelberg planner producing the
per-cohort weights each step.

Proves end-to-end that the sharded train_step (params sharded per
repro.sharding rules, MoE expert-parallel shard_map, eq.-34 weighted loss)
EXECUTES and optimizes, and that per-cohort selection changes which data
influences the model.

  PYTHONPATH=src python -m repro.launch.multidevice_demo --arch granite-moe-3b-a800m-smoke
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..core import RoundPolicy, WirelessConfig, init_aou, sample_topology
from ..data.pipeline import synthetic_lm_stream
from ..models.moe import ShardCtx
from ..models.transformer import init_params, param_count
from ..sharding.partition import batch_shardings, param_shardings, opt_state_shardings
from ..train.optimizer import make_optimizer
from ..train.train_step import make_train_step
from .mesh import dp_axes_of, smoke_mesh
from .train import fl_round_weights


def run(arch: str = "granite-moe-3b-a800m-smoke", steps: int = 8,
        batch: int = 8, seq: int = 64, data: int = 4, model: int = 2,
        seed: int = 0) -> list[float]:
    assert jax.device_count() >= data * model, (
        f"need {data*model} devices, have {jax.device_count()} "
        "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    mesh = smoke_mesh(data, model)
    ctx = ShardCtx(mesh=mesh, dp_axes=("data",))
    cfg = get_config(arch)

    params = init_params(cfg, jax.random.PRNGKey(seed), ep_size=model)
    p_sh = param_shardings(jax.eval_shape(lambda: params), mesh)
    params = jax.device_put(params, p_sh)
    print(f"{cfg.name}: {param_count(params)/1e6:.2f}M params on "
          f"{data}x{model} mesh ({jax.device_count()} devices)")

    opt = make_optimizer("adamw", 1e-3)
    opt_state = opt.init(params)
    o_sh = opt_state_shardings(jax.eval_shape(lambda: opt_state), p_sh, mesh)
    opt_state = jax.device_put(opt_state, o_sh)

    step_fn = jax.jit(make_train_step(cfg, opt, ctx, remat=False),
                      donate_argnums=(0, 1))

    # Stackelberg planner drives per-cohort weights (cohort = batch row).
    rng = np.random.default_rng(seed)
    wcfg = WirelessConfig(n_devices=batch, n_subchannels=max(2, batch // 2))
    fl_state = {"topo": sample_topology(rng, wcfg), "aou": init_aou(batch)}
    beta = rng.integers(10, 50, batch).astype(np.float64)
    stream = synthetic_lm_stream(seed, batch, seq, cfg.vocab)

    losses = []
    t0 = time.time()
    for step in range(steps):
        b = next(stream)
        w, plan, lat = fl_round_weights(fl_state, beta, wcfg, rng, RoundPolicy())
        if w.sum() == 0:
            w = np.ones(batch)
        ex = {
            "tokens": jnp.asarray(b["tokens"]),
            "labels": jnp.asarray(b["labels"]),
            "fl_weights": jnp.asarray(w, jnp.float32),
        }
        ex = jax.device_put(ex, batch_shardings(jax.eval_shape(lambda: ex), mesh, ("data",)))
        params, opt_state, m = step_fn(params, opt_state, ex)
        losses.append(float(m["loss"]))
        print(f"step {step} loss {losses[-1]:.4f} "
              f"tx={int(plan.transmitted.sum())}/{batch} latency={lat:.2f}s")
    print(f"{steps} sharded steps in {time.time()-t0:.1f}s; "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    assert losses[-1] < losses[0], "loss must decrease"
    return losses


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-moe-3b-a800m-smoke")
    ap.add_argument("--steps", type=int, default=8)
    a = ap.parse_args(argv)
    run(a.arch, steps=a.steps)


if __name__ == "__main__":
    main()
