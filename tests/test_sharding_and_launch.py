"""Sharding rules + HLO analysis + (subprocess) a real dry-run combo."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch.analytic import HW, analytic_cost, model_flops, param_counts
from repro.launch.hlo_analysis import (
    collective_stats, parse_computations, while_trip_counts)
from repro.configs.base import INPUT_SHAPES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _FakeMesh:
    shape = {"model": 16, "data": 16}
    axis_names = ("data", "model")


def test_param_specs_rules():
    from repro.models.transformer import init_params
    from repro.sharding.partition import param_spec

    cfg = get_config("yi-6b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    mesh = _FakeMesh()
    flat = jax.tree_util.tree_leaves_with_path(params)
    specs = {
        "/".join(str(getattr(k, "key", k)) for k in path): param_spec(path, leaf, mesh)
        for path, leaf in flat
    }
    # fan-out projections shard last dim; fan-in shard first (after stack dim)
    assert specs["s0_l0/attn/wq/w"] == P(None, None, "model")
    assert specs["s0_l0/attn/wo/w"] == P(None, "model", None)
    assert specs["s0_l0/ffn/down/w"] == P(None, "model", None)
    assert specs["s0_l0/ln1/g"] == P(None, None)
    assert specs["lm_head/w"] == P(None, "model")


def test_param_specs_moe_and_odd_vocab():
    from repro.models.transformer import init_params
    from repro.sharding.partition import param_spec

    mesh = _FakeMesh()
    cfg = get_config("granite-moe-3b-a800m").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0), ep_size=2)
    flat = jax.tree_util.tree_leaves_with_path(params)
    specs = {
        "/".join(str(getattr(k, "key", k)) for k in path): param_spec(path, leaf, mesh)
        for path, leaf in flat
    }
    # expert bank: (L, E_pad, d, ff); E_pad=4 not divisible by 16 -> replicated,
    # but at full scale E=48 shards (validated in the dry-run itself).
    assert specs["s0_l0/moe/gate"][0] is None
    # whisper's 51865 vocab is not divisible by 16 -> embed replicated
    wcfg = get_config("whisper-base")
    import jax.numpy as jnp
    fake_embed = jax.ShapeDtypeStruct((wcfg.vocab, wcfg.d_model), jnp.bfloat16)
    from jax.tree_util import DictKey
    spec = param_spec((DictKey("embed"), DictKey("w")), fake_embed, mesh)
    assert spec == P(None, None)


def test_hlo_collective_parse_and_trip_counts():
    hlo = """
HloModule m

%body.1 (p: (s32[], f32[128])) -> (s32[], f32[128]) {
  %x = f32[128]{0} all-reduce(f32[128]{0} %y), replica_groups={}
  ROOT %t = (s32[], f32[128]) tuple(%i, %x)
}

%cond.1 (p: (s32[], f32[128])) -> pred[] {
  %c = s32[] constant(28)
  ROOT %cmp = pred[] compare(s32[] %i, s32[] %c), direction=LT
}

ENTRY %main () -> f32[128] {
  %w = (s32[], f32[128]) while((s32[], f32[128]) %init), condition=%cond.1, body=%body.1
  %g = bf16[64]{0} all-gather(bf16[32]{0} %z), dimensions={0}
  ROOT %r = f32[128] get-tuple-element(%w), index=1
}
"""
    comps = parse_computations(hlo)
    assert set(comps) >= {"body.1", "cond.1", "main"}
    trips = while_trip_counts(comps)
    assert trips["body.1"] == 28
    stats = collective_stats(hlo)
    assert stats["all-reduce"] == 28 * 128 * 4          # loop-corrected
    assert stats["all-gather"] == 64 * 2
    assert stats["raw_total"] == 128 * 4 + 64 * 2


def test_analytic_param_counts_match_real():
    """Analytic N within 2% of the actual parameter tree for every arch
    (full config via eval_shape -- no allocation)."""
    from repro.models.transformer import init_params

    for arch in ("qwen2-7b", "yi-6b", "rwkv6-7b", "granite-moe-3b-a800m",
                 "jamba-v0.1-52b", "deepseek-v3-671b", "qwen1.5-110b"):
        cfg = get_config(arch)
        shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
        real = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))
        pred = param_counts(cfg)["total"]
        assert abs(pred - real) / real < 0.02, (arch, pred, real)


def test_analytic_flops_sane():
    cfg = get_config("qwen2-7b")
    shape = INPUT_SHAPES["train_4k"]
    mf = model_flops(cfg, shape)
    # 6ND within 25% of the analytic forward*3 for a dense model
    assert 0.75 < mf["six_nd_active"] / mf["train_total"] < 1.25
    roof = analytic_cost(cfg, shape, HW(chips=256))
    assert roof["dominant"] == "compute_s"
    assert 0.8 < roof["useful_ratio"] < 1.25


def test_known_param_totals():
    """Headline parameter counts match the papers' names (within 15%)."""
    expect = {
        "deepseek-v3-671b": 671e9,
        "qwen1.5-110b": 111e9,
        "qwen2-7b": 7.6e9,
        "yi-6b": 6.1e9,
        "jamba-v0.1-52b": 52e9,
        "rwkv6-7b": 7.0e9,
    }
    for arch, n in expect.items():
        got = param_counts(get_config(arch))["total"]
        assert abs(got - n) / n < 0.15, (arch, got / 1e9)


@pytest.mark.slow
def test_dryrun_subprocess_one_combo():
    """End-to-end deliverable (e) check: a full lower+compile on the 16x16
    mesh in a fresh process (512 forced host devices)."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "whisper-base", "--shape", "decode_32k"],
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": os.path.join(REPO, "src")},
        capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "1 passed, 0 failed" in out.stdout


@pytest.mark.slow
def test_multidevice_execution_subprocess():
    """EXECUTE (not just compile) sharded FL-weighted train steps on an
    8-device host mesh: loss must decrease."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.multidevice_demo", "--steps", "4"],
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": os.path.join(REPO, "src")},
        capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "loss" in out.stdout


# --------------------------------------------------------------------------
# device-axis sharding of the whole-horizon Γ solve (DESIGN.md §13)
# --------------------------------------------------------------------------

def test_gamma_shard_matches_vmap_single_device():
    """shard=True on one device must go through the shard_map path and
    still be bit-identical to the plain vmap dispatch."""
    from repro.core import WirelessConfig, solve_pairs_fused

    rng = np.random.default_rng(13)
    n = 96
    cfg = WirelessConfig()
    beta = rng.integers(5, 60, n).astype(float)
    h2 = rng.exponential(size=(4, n)) * 3
    sh = solve_pairs_fused(beta[None, :], h2, cfg, shard=True)
    un = solve_pairs_fused(beta[None, :], h2, cfg, shard=False)
    for field in ("feasible", "iterations", "tau", "p", "time_s", "energy_j"):
        np.testing.assert_array_equal(getattr(sh, field), getattr(un, field),
                                      err_msg=field)


@pytest.mark.slow
def test_gamma_shard_two_devices_subprocess():
    """shard=True on 2 forced host devices == unsharded, bit-for-bit, with
    a pad-and-drop row count that does NOT divide the device count
    (separate process: device count must be set before JAX initializes)."""
    code = """
import numpy as np
from repro.core import WirelessConfig, solve_pairs_fused
cfg = WirelessConfig()
rng = np.random.default_rng(17)
n = 77                                     # K*n odd vs 2 devices: pad-and-drop
beta = rng.integers(5, 60, n).astype(float)
h2 = rng.exponential(size=(3, 4, n)) * 3   # whole-horizon tensor
sh = solve_pairs_fused(beta[None, None, :], h2, cfg, shard=True)
un = solve_pairs_fused(beta[None, None, :], h2, cfg, shard=False)
for field in ("feasible", "iterations", "tau", "p", "time_s", "energy_j"):
    assert np.array_equal(getattr(sh, field), getattr(un, field),
                          equal_nan=True), field
print("GAMMA_SHARD_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=2"),
               PYTHONPATH=os.path.join(REPO, "src") + os.pathsep +
                          os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "GAMMA_SHARD_OK" in proc.stdout
