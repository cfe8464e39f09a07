"""Attention blocks: GQA (+QKV bias, RoPE / M-RoPE, sliding window, chunked
softmax for long prefill), deepseek-style MLA with latent KV cache, and
whisper-style cross-attention.

Grouped heads never materialize the repeated K/V: queries are reshaped to
(B, S, Hkv, G, Dh) and contracted against (B, S, Hkv, Dh) directly, which
also keeps the head axis shardable on the `model` mesh axis.

Caches (decode path) are ring buffers:
    {"k": (B, C, Hkv, Dh), "v": (B, C, Hkv, Dh), "pos": (C,) int32 global
     positions (-1 = empty), "idx": () int32 next write slot}
K is stored *with RoPE applied at its true position*, so decode never
re-rotates the cache.  Sliding-window configs simply allocate C = window.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .layers import DTYPE, apply_mrope, apply_rope, dense, dense_init

__all__ = [
    "gqa_init",
    "gqa_forward",
    "gqa_decode",
    "init_kv_cache",
    "mla_init",
    "mla_forward",
    "mla_decode",
    "init_mla_cache",
    "cross_attn_init",
    "cross_attn",
]

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Core softmax attention on grouped heads.
# --------------------------------------------------------------------------

def _sdpa(q, k, v, mask, scale):
    """q: (B,Sq,Hkv,G,Dh); k,v: (B,Sk,Hkv,Dh); mask: (B,1,1,Sq,Sk) or None."""
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32), k.astype(jnp.float32))
    logits = logits * scale
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out


def _causal_mask(sq: int, sk: int, q_offset, window: int):
    """(1,1,1,Sq,Sk) boolean; window = 0 means full causal."""
    qi = jnp.arange(sq)[:, None] + q_offset
    kj = jnp.arange(sk)[None, :]
    m = kj <= qi
    if window > 0:
        m &= kj > qi - window
    return m[None, None, None]


def _chunked_sdpa(q, k, v, scale, window: int, chunk: int, q_offset: int = 0):
    """Flash-style: scan over query chunks with a streaming softmax.

    Peak memory per step is (B,Hkv,G,chunk,Sk) instead of (...,Sq,Sk); this
    is the memory-term optimization used for the 32k-prefill shapes
    (EXPERIMENTS.md §Perf).  q_offset shifts the causal mask for
    sequence-parallel shards.
    """
    b, sq, hkv, g, dh = q.shape
    dv = v.shape[-1]  # MLA: value dim differs from the q/k dim
    sk = k.shape[1]
    n_chunks = sq // chunk
    assert sq % chunk == 0, (sq, chunk)
    qc = q.reshape(b, n_chunks, chunk, hkv, g, dh).transpose(1, 0, 2, 3, 4, 5)

    def step(_, args):
        i, qi = args
        offset = i * chunk + q_offset
        mask = _causal_mask(chunk, sk, offset, window)
        out = _sdpa(qi, k, v, mask, scale)
        return None, out

    _, outs = jax.lax.scan(step, None, (jnp.arange(n_chunks), qc))
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, hkv, g, dv)


def _full_attn(qg, k, v, scale, window: int, chunk: int, q_offset=0):
    """Dispatch: chunked scan for long sequences, one-shot otherwise."""
    s = qg.shape[1]
    if chunk and s > 2 * chunk:
        return _chunked_sdpa(qg, k, v, scale, window, chunk, q_offset)
    mask = _causal_mask(s, k.shape[1], q_offset, window)
    return _sdpa(qg, k, v, mask, scale)


def sharded_causal_attention(qg, k, v, scale, window: int, chunk: int, ctx):
    """Explicitly partitioned full-sequence causal attention (shard_map).

    Baseline GSPMD sometimes partial-sums the per-chunk score matrix over
    the model axis (an all-reduce of (B,H,chunk,Sk) PER layer PER chunk —
    the dominant collective in the baseline roofline).  This wrapper pins a
    communication-free layout instead:

      * head-parallel when Hkv %% model == 0: every mesh column owns
        Hkv/model kv-head groups for the full sequence; zero collectives
        inside attention (q/k/v arrive head-sharded from their matmuls).
      * sequence-parallel otherwise: every column owns Sq/model query rows
        and replicates K/V (one all-gather of K/V per layer, ~|K|+|V|
        bytes, vs. the baseline's per-chunk score all-reduce).

    qg: (B, S, Hkv, G, Dh); k, v: (B, S, Hkv, Dh*).
    """
    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh
    mp = mesh.shape["model"]
    b, s, hkv, g, dh = qg.shape
    dp = ctx.dp_axes
    b_ax = dp if b % max(1, _dp_size(ctx)) == 0 else None

    if hkv % mp == 0:
        # ---- head-parallel ------------------------------------------------
        def fn(q_l, k_l, v_l):
            return _full_attn(q_l, k_l, v_l, scale, window, chunk)

        return jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(b_ax, None, "model", None, None),
                      P(b_ax, None, "model", None),
                      P(b_ax, None, "model", None)),
            out_specs=P(b_ax, None, "model", None, None),
            check_vma=False,
        )(qg, k, v)

    if s % mp == 0:
        # ---- sequence-parallel ---------------------------------------------
        s_loc = s // mp

        def fn(q_l, k_f, v_f):
            off = jax.lax.axis_index("model") * s_loc
            return _full_attn(q_l, k_f, v_f, scale, window,
                              min(chunk, s_loc) if chunk else 0, off)

        return jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(b_ax, "model", None, None, None),
                      P(b_ax, None, None, None),
                      P(b_ax, None, None, None)),
            out_specs=P(b_ax, "model", None, None, None),
            check_vma=False,
        )(qg, k, v)

    # Fallback: GSPMD auto.
    return _full_attn(qg, k, v, scale, window, chunk)


def _dp_size(ctx) -> int:
    n = 1
    for a in ctx.dp_axes:
        n *= ctx.mesh.shape[a]
    return n


# --------------------------------------------------------------------------
# GQA
# --------------------------------------------------------------------------

def gqa_init(key, cfg: ArchConfig):
    dh = cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, cfg.d_model, cfg.n_heads * dh, bias=cfg.qkv_bias),
        "wk": dense_init(k2, cfg.d_model, cfg.n_kv_heads * dh, bias=cfg.qkv_bias),
        "wv": dense_init(k3, cfg.d_model, cfg.n_kv_heads * dh, bias=cfg.qkv_bias),
        "wo": dense_init(k4, cfg.n_heads * dh, cfg.d_model),
    }


def _project_qkv(p, cfg: ArchConfig, x, positions, mrope_pos):
    b, s, _ = x.shape
    dh = cfg.head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    q = dense(p["wq"], x).reshape(b, s, hq, dh)
    k = dense(p["wk"], x).reshape(b, s, hkv, dh)
    v = dense(p["wv"], x).reshape(b, s, hkv, dh)
    if cfg.use_mrope and mrope_pos is not None:
        sections = _mrope_sections(dh)
        q = apply_mrope(q, mrope_pos, cfg.rope_theta, sections)
        k = apply_mrope(k, mrope_pos, cfg.rope_theta, sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mrope_sections(dh: int):
    """Split Dh/2 frequency pairs into (t, h, w) ~ (1/4, 3/8, 3/8)."""
    half = dh // 2
    t = half // 4
    h = (half - t) // 2
    return (t, h, half - t - h)


def gqa_forward(
    p,
    cfg: ArchConfig,
    x,
    *,
    positions=None,
    mrope_pos=None,
    chunk: int = 0,
    causal: bool = True,
    return_kv: bool = False,
    ctx=None,
):
    """Training / prefill self-attention (causal, optional sliding window).

    With return_kv=True also returns the rotated (k, v) so the serving path
    can seed a decode cache from prefill.  ctx with attn_shard="explicit"
    routes through sharded_causal_attention (§Perf).
    """
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)[None, :]
    q, k, v = _project_qkv(p, cfg, x, positions, mrope_pos)
    hkv = cfg.n_kv_heads
    g = cfg.n_heads // hkv
    dh = cfg.head_dim
    qg = q.reshape(b, s, hkv, g, dh)
    scale = dh**-0.5
    if not causal:
        out = _sdpa(qg, k, v, None, scale)
    elif cfg.attn_impl == "pallas" and (ctx is None or ctx.mesh is None):
        # Single-device flash kernel (TPU Mosaic; interpret on CPU).
        from ..kernels.flash_attention.ops import flash_attention
        out = flash_attention(q, k, v, causal=True, window=cfg.sliding_window,
                              bq=min(128, s), bk=min(128, s))
        out = out.reshape(b, s, hkv, g, dh)
    elif ctx is not None and getattr(ctx, "mesh", None) is not None \
            and getattr(ctx, "attn_shard", "auto") == "explicit":
        out = sharded_causal_attention(qg, k, v, scale, cfg.sliding_window,
                                       chunk, ctx)
    else:
        out = _full_attn(qg, k, v, scale, cfg.sliding_window, chunk)
    y = dense(p["wo"], out.reshape(b, s, cfg.n_heads * dh))
    if return_kv:
        return y, (k, v)
    return y


def init_kv_cache(cfg: ArchConfig, batch: int, cache_len: int, dtype=DTYPE):
    dh = cfg.head_dim
    return {
        "k": jnp.zeros((batch, cache_len, cfg.n_kv_heads, dh), dtype),
        "v": jnp.zeros((batch, cache_len, cfg.n_kv_heads, dh), dtype),
        "pos": jnp.full((cache_len,), -1, jnp.int32),
        "idx": jnp.zeros((), jnp.int32),
    }


def gqa_decode(p, cfg: ArchConfig, x, cache, cur_pos, *, mrope_pos=None):
    """One-token decode: x (B, 1, d); cur_pos () int32 global position."""
    b = x.shape[0]
    dh = cfg.head_dim
    hkv = cfg.n_kv_heads
    g = cfg.n_heads // hkv
    positions = jnp.full((b, 1), cur_pos, jnp.int32)
    q, k, v = _project_qkv(p, cfg, x, positions, mrope_pos)

    c = cache["k"].shape[1]
    slot = cache["idx"] % c
    new_k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    new_v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    new_pos = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], jnp.full((1,), cur_pos, jnp.int32), slot, axis=0
    )

    valid = (new_pos >= 0) & (new_pos <= cur_pos)
    if cfg.sliding_window > 0:
        valid &= new_pos > cur_pos - cfg.sliding_window
    mask = valid[None, None, None, None, :]                    # (1,1,1,1,C)

    qg = q.reshape(b, 1, hkv, g, dh)
    out = _sdpa(qg, new_k, new_v, mask, dh**-0.5)
    y = dense(p["wo"], out.reshape(b, 1, cfg.n_heads * dh))
    new_cache = {"k": new_k, "v": new_v, "pos": new_pos, "idx": cache["idx"] + 1}
    return y, new_cache


# --------------------------------------------------------------------------
# MLA (deepseek-v3): low-rank latent KV, decoupled RoPE key.
# --------------------------------------------------------------------------

def mla_init(key, cfg: ArchConfig):
    ks = jax.random.split(key, 6)
    h = cfg.n_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "q_down": dense_init(ks[0], cfg.d_model, cfg.q_lora_rank),
        "q_up": dense_init(ks[1], cfg.q_lora_rank, h * qk),
        "kv_down": dense_init(ks[2], cfg.d_model, cfg.kv_lora_rank + cfg.qk_rope_dim),
        "kv_up": dense_init(ks[3], cfg.kv_lora_rank, h * (cfg.qk_nope_dim + cfg.v_head_dim)),
        "wo": dense_init(ks[4], h * cfg.v_head_dim, cfg.d_model),
    }


def _mla_qkv_from_latent(p, cfg: ArchConfig, xq, c_kv, k_pe):
    """Up-project: returns q (B,Sq,H,qk), k (B,Sk,H,qk), v (B,Sk,H,dv).

    xq: query-side activations; (c_kv, k_pe) the latent cache (key side).
    """
    b, sq, _ = xq.shape
    sk = c_kv.shape[1]
    h, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q = dense(p["q_up"], dense(p["q_down"], xq)).reshape(b, sq, h, dn + dr)
    kv = dense(p["kv_up"], c_kv).reshape(b, sk, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (b, sk, h, dr))], -1)
    return q, k, v


def mla_forward(p, cfg: ArchConfig, x, *, positions=None, chunk: int = 0,
                return_kv: bool = False, ctx=None):
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)[None, :]
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    down = dense(p["kv_down"], x)
    c_kv, k_pe = down[..., : cfg.kv_lora_rank], down[..., cfg.kv_lora_rank :]
    k_pe = apply_rope(k_pe[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    q, k, v = _mla_qkv_from_latent(p, cfg, x, c_kv, k_pe)
    # Rotate the rope-section of q at query positions.
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)
    q = jnp.concatenate([q_nope, q_pe], -1)

    scale = (dn + dr) ** -0.5
    qg = q[:, :, :, None, :]  # Hkv = H, G = 1
    if ctx is not None and getattr(ctx, "mesh", None) is not None \
            and getattr(ctx, "attn_shard", "auto") == "explicit":
        # MLA is post-up-projection MHA (Hkv = 128) -> head-parallel path.
        out = sharded_causal_attention(qg, k, v, scale, cfg.sliding_window,
                                       chunk, ctx)
    else:
        out = _full_attn(qg, k, v, scale, cfg.sliding_window, chunk)
    out = out[:, :, :, 0, :]
    y = dense(p["wo"], out.reshape(b, s, cfg.n_heads * cfg.v_head_dim))
    if return_kv:
        return y, (c_kv, k_pe)
    return y


def init_mla_cache(cfg: ArchConfig, batch: int, cache_len: int, dtype=DTYPE):
    """The MLA decode cache stores the *latent* (kv_lora + rope) per token —
    the paper-exact memory win of MLA (5.4x smaller than GQA kv=128)."""
    return {
        "c_kv": jnp.zeros((batch, cache_len, cfg.kv_lora_rank), dtype),
        "k_pe": jnp.zeros((batch, cache_len, cfg.qk_rope_dim), dtype),
        "pos": jnp.full((cache_len,), -1, jnp.int32),
        "idx": jnp.zeros((), jnp.int32),
    }


def mla_decode(p, cfg: ArchConfig, x, cache, cur_pos):
    """MLA single-token decode. Two execution modes:

    * naive (paper-faithful baseline): up-project the ENTIRE latent cache to
      per-head K/V, then standard attention — materializes
      (B, C, H, dn+dv) every step;
    * absorbed (cfg.mla_absorb, EXPERIMENTS §Perf): fold kv_up into the
      query/output projections so attention runs in the 576-dim latent
      space — the cache is read once and no per-head K/V ever exists.
      Identical math (associativity of the matmuls).
    """
    b = x.shape[0]
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    positions = jnp.full((b, 1), cur_pos, jnp.int32)
    down = dense(p["kv_down"], x)
    c_kv_new, k_pe_new = down[..., : cfg.kv_lora_rank], down[..., cfg.kv_lora_rank :]
    k_pe_new = apply_rope(k_pe_new[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]

    c = cache["c_kv"].shape[1]
    slot = cache["idx"] % c
    c_kv = jax.lax.dynamic_update_slice_in_dim(cache["c_kv"], c_kv_new, slot, axis=1)
    k_pe = jax.lax.dynamic_update_slice_in_dim(cache["k_pe"], k_pe_new, slot, axis=1)
    pos = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], jnp.full((1,), cur_pos, jnp.int32), slot, axis=0
    )
    new_cache = {"c_kv": c_kv, "k_pe": k_pe, "pos": pos, "idx": cache["idx"] + 1}

    valid = (pos >= 0) & (pos <= cur_pos)
    if cfg.sliding_window > 0:
        valid &= pos > cur_pos - cfg.sliding_window

    if getattr(cfg, "mla_absorb", False):
        h, dv = cfg.n_heads, cfg.v_head_dim
        q = dense(p["q_up"], dense(p["q_down"], x)).reshape(b, 1, h, dn + dr)
        q_nope, q_pe = q[..., :dn], q[..., dn:]
        q_pe = apply_rope(q_pe, positions, cfg.rope_theta)
        w_up = p["kv_up"]["w"].reshape(cfg.kv_lora_rank, h, dn + dv)
        w_k, w_v = w_up[..., :dn], w_up[..., dn:]
        # Absorb kv_up into q: scores live in latent space.
        q_abs = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(jnp.float32),
                           w_k.astype(jnp.float32))
        logits = jnp.einsum("bqhr,bcr->bhqc", q_abs, c_kv.astype(jnp.float32))
        logits += jnp.einsum("bqhd,bcd->bhqc", q_pe.astype(jnp.float32),
                             k_pe.astype(jnp.float32))
        logits *= (dn + dr) ** -0.5
        logits = jnp.where(valid[None, None, None, :], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        o_lat = jnp.einsum("bhqc,bcr->bqhr", probs, c_kv.astype(jnp.float32))
        out = jnp.einsum("bqhr,rhd->bqhd", o_lat, w_v.astype(jnp.float32))
        out = out.astype(x.dtype)
    else:
        q, k, v = _mla_qkv_from_latent(p, cfg, x, c_kv, k_pe)
        q_nope, q_pe = q[..., :dn], q[..., dn:]
        q_pe = apply_rope(q_pe, positions, cfg.rope_theta)
        q = jnp.concatenate([q_nope, q_pe], -1)
        mask = valid[None, None, None, None, :]
        out = _sdpa(q[:, :, :, None, :], k, v, mask, (dn + dr) ** -0.5)[:, :, :, 0, :]
    y = dense(p["wo"], out.reshape(b, 1, cfg.n_heads * cfg.v_head_dim))
    return y, new_cache


# --------------------------------------------------------------------------
# Cross-attention (whisper decoder -> encoder output)
# --------------------------------------------------------------------------

def cross_attn_init(key, cfg: ArchConfig):
    dh = cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, cfg.d_model, cfg.n_heads * dh, bias=cfg.qkv_bias),
        "wk": dense_init(k2, cfg.d_model, cfg.n_kv_heads * dh),
        "wv": dense_init(k3, cfg.d_model, cfg.n_kv_heads * dh),
        "wo": dense_init(k4, cfg.n_heads * dh, cfg.d_model),
    }


def cross_attn(p, cfg: ArchConfig, x, enc_out):
    """x: (B, Sq, d) decoder stream; enc_out: (B, Se, d). No mask, no RoPE
    (whisper uses learned/sinusoidal absolute positions on the encoder)."""
    b, sq, _ = x.shape
    se = enc_out.shape[1]
    dh = cfg.head_dim
    hkv = cfg.n_kv_heads
    g = cfg.n_heads // hkv
    q = dense(p["wq"], x).reshape(b, sq, cfg.n_heads, dh)
    k = dense(p["wk"], enc_out).reshape(b, se, hkv, dh)
    v = dense(p["wv"], enc_out).reshape(b, se, hkv, dh)
    out = _sdpa(q.reshape(b, sq, hkv, g, dh), k, v, None, dh**-0.5)
    return dense(p["wo"], out.reshape(b, sq, cfg.n_heads * dh))
