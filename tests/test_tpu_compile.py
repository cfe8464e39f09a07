"""Compile rehearsals for a described TPU v5e (nothing runs, no chip needed).

The TPU compiler is installed alongside JAX, and it compiles for a chip
that is described rather than attached.  These tests compile the main
path's kernels and the client training step at their real widths, so a
Pallas body or a shape that Mosaic/XLA:TPU would refuse fails here, on
the CPU, instead of on the chip.  Interpret mode cannot show this: the
fused Γ kernel passed every interpret test while its TPU compile was
refused (a scatter in the store initialisation, a bool loop carry).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and the test workers all import this file.
"""
import pytest

import jax
import jax.numpy as jnp

from repro.core import WirelessConfig

# N = 32768 devices x K = 4 sub-channels of pairs in (rows, 128) tiles.
ROWS = 32768 * 4 // 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _wireless_kw(cfg):
    return dict(kappa0_mu=cfg.kappa0 * cfg.mu_cycles, cpu_hz=cfg.cpu_hz,
                pt_w=cfg.pt_w, model_bits=cfg.model_bits,
                bandwidth_hz=cfg.bandwidth_hz)


def _compiled_text(fn, args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_polyblock_solve_kernel_compiles_for_v5e(one_chip):
    from repro.kernels.polyblock_fused.kernel import polyblock_solve_call

    cfg = WirelessConfig()
    x = jax.ShapeDtypeStruct((ROWS, 128), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda b, h, e: polyblock_solve_call(
            b, h, e, eps=0.01, max_iter=64, n_bisect=60,
            mu_cycles=cfg.mu_cycles, **_wireless_kw(cfg)),
        (x, x, x))
    assert "tpu_custom_call" in text


def test_polyblock_project_kernel_compiles_for_v5e(one_chip):
    from repro.kernels.polyblock_project.kernel import polyblock_project_call

    cfg = WirelessConfig()
    x = jax.ShapeDtypeStruct((ROWS, 128), jnp.float32, sharding=one_chip)
    # The step driver calls the projection inside its float64 jit steps, so
    # the kernel must compile with x64 on as well (no 64-bit index enters).
    with jax.enable_x64(True):
        text = _compiled_text(
            lambda t, p, b, h, e: polyblock_project_call(
                t, p, b, h, e, n_bisect=60, **_wireless_kw(cfg)),
            (x,) * 5)
    assert "tpu_custom_call" in text


def test_mnist_client_trainer_compiles_for_v5e(one_chip):
    from repro.fl.sim import SimConfig, _group_trainer_and_policies, _prepare

    cfg = SimConfig(dataset="mnist", n_devices=64, n_subchannels=16,
                    rounds=1)
    model, trainer, _, _ = _group_trainer_and_policies([cfg])
    bmax = int(_prepare(cfg).part.beta.max())
    k = cfg.n_subchannels

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    args = (params, spec((k, bmax, 784), jnp.float32),
            spec((k, bmax), jnp.int32), spec((k, bmax), jnp.float32),
            spec((k, 2), jnp.uint32))
    compiled = jax.jit(trainer).lower(*args).compile()
    # K stacked copies of the 784-128-256-10 MLP come back.
    shapes = jax.tree_util.tree_map(lambda a: a.shape, compiled.out_info)
    assert shapes["fc1"]["w"] == (k, 784, 128)
    assert shapes["fc2"]["w"] == (k, 128, 256)
    assert shapes["out"]["w"] == (k, 256, 10)
