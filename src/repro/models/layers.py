"""Core transformer layers: RMSNorm, RoPE, GQA attention, gated MLP.

Pure-functional: every layer is ``fn(params, x, ...) -> y`` over plain dict
pytrees.  Attention supports three execution paths:

  * ``naive``   -- full [S, T] logits; used for short sequences / smoke tests.
  * ``blocked`` -- lax.scan over query chunks (flash-style online softmax in
    fp32 accumulators); bounded memory for 32k+ prefill on any backend.
  * ``pallas``  -- the Pallas TPU kernel in ``repro.kernels`` (opt-in; the
    dry-run uses XLA paths because Pallas does not lower on CPU hosts).

Sliding-window attention is supported on every path; the blocked path can
additionally *slice* the KV range per query chunk (``window_slice=True``)
so windowed attention is sub-quadratic in compute, not just masked — this is
one of the beyond-paper roofline optimizations (EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import ModelConfig

Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, shape, in_axis_size=None, dtype=jnp.float32):
    """Truncated-normal fan-in init (maxtext-style 1/sqrt(fan_in))."""
    fan_in = in_axis_size if in_axis_size is not None else shape[0]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * 0.02


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rms_norm(scale: jax.Array, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm in fp32 accumulation, cast back to input dtype."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def init_rms_norm(d: int) -> jax.Array:
    # Stored as (scale - 1) so zero-init == identity (gemma convention).
    return jnp.zeros((d,), jnp.float32)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, D]; positions broadcastable to [..., S]."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, D/2]
    angles = angles[..., None, :]                      # [..., S, 1, D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "norm": init_rms_norm(d),
        "wq": dense_init(ks[0], (d, h, hd), in_axis_size=d),
        "wk": dense_init(ks[1], (d, kv, hd), in_axis_size=d),
        "wv": dense_init(ks[2], (d, kv, hd), in_axis_size=d),
        "wo": dense_init(ks[3], (h, hd, d), in_axis_size=h * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), jnp.float32)
        p["bk"] = jnp.zeros((kv, hd), jnp.float32)
        p["bv"] = jnp.zeros((kv, hd), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = init_rms_norm(hd)
        p["k_norm"] = init_rms_norm(hd)
    return p


def _qkv(params: Params, cfg: ModelConfig, x: jax.Array,
         positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    dtype = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dtype)
        k = k + params["bk"].astype(dtype)
        v = v + params["bv"].astype(dtype)
    if cfg.qk_norm:
        q = rms_norm(params["q_norm"], q, cfg.norm_eps)
        k = rms_norm(params["k_norm"], k, cfg.norm_eps)
    if cfg.rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array,
               window: int) -> jax.Array:
    """Additive mask [.., Sq, Sk]: causal (+ sliding window if window>0)."""
    ok = k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        ok &= k_pos[None, :] > (q_pos[:, None] - window)
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array, bias: jax.Array,
          scale: float) -> jax.Array:
    """Grouped scaled-dot-product attention.

    q: [B, Sq, H, D]; k, v: [B, Sk, KV, D]; bias: [Sq, Sk] additive.
    """
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, d)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    logits = logits * scale + bias
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, sq, h, d)


def _blocked_attention(q, k, v, q_positions, k_positions, window, scale,
                       block_q=1024, window_slice=False):
    """lax.scan over query chunks with online-softmax fp32 accumulators.

    When ``window_slice`` and a sliding window is active, each query chunk
    only reads a dynamic slice of KV of length (window + block_q), making
    compute O(S * window) instead of O(S^2).
    """
    b, s, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    nblocks = -(-s // block_q)
    pad = nblocks * block_q - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, (0, pad), constant_values=-1)
    qb = q.reshape(b, nblocks, block_q, h, d).transpose(1, 0, 2, 3, 4)
    pb = q_positions.reshape(nblocks, block_q)

    use_slice = window_slice and window > 0
    kv_span = min(window + block_q, k.shape[1]) if use_slice else k.shape[1]

    def body(_, inputs):
        qi, qpos, iblk = inputs
        if use_slice:
            start = jnp.maximum(iblk * block_q + block_q - kv_span, 0)
            start = jnp.minimum(start, k.shape[1] - kv_span)
            ki = lax.dynamic_slice_in_dim(k, start, kv_span, axis=1)
            vi = lax.dynamic_slice_in_dim(v, start, kv_span, axis=1)
            kpos = lax.dynamic_slice_in_dim(k_positions, start, kv_span)
        else:
            ki, vi, kpos = k, v, k_positions
        bias = _mask_bias(qpos, kpos, window)
        out = _sdpa(qi, ki, vi, bias, scale)
        return None, out

    iblk = jnp.arange(nblocks)
    # a block's logits are recomputed in the backward pass, not kept for
    # every block: a training step holds one block's [Sq_blk, S] logits
    _, outs = lax.scan(jax.checkpoint(body), None, (qb, pb, iblk))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nblocks * block_q, h, d)
    return out[:, :s]


def attention(params: Params, cfg: ModelConfig, x: jax.Array,
              positions: jax.Array, impl: str = "auto",
              window_slice: bool = False) -> jax.Array:
    """Full-sequence causal attention (train / prefill)."""
    q, k, v = _qkv(params, cfg, x, positions)
    scale = cfg.attn_scale
    s = x.shape[1]
    if impl == "auto":
        impl = "naive" if s <= 2048 else "blocked"
    if impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(q, k, v, causal=True,
                                     window=cfg.sliding_window)
    elif impl == "blocked":
        out = _blocked_attention(q, k, v, positions, positions,
                                 cfg.sliding_window, scale,
                                 window_slice=window_slice)
    else:
        bias = _mask_bias(positions, positions, cfg.sliding_window)
        out = _sdpa(q, k, v, bias, scale)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))


def attention_fill(params: Params, cfg: ModelConfig, x: jax.Array,
                   positions: jax.Array, cache_k: jax.Array,
                   cache_v: jax.Array, impl: str = "auto",
                   window_slice: bool = False
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full-sequence attention that also fills the KV cache (prefill).

    Writes K/V for positions [0, S) into the cache and returns the same
    output as ``attention``.
    """
    q, k, v = _qkv(params, cfg, x, positions)
    scale = cfg.attn_scale
    s = x.shape[1]
    cache_k = lax.dynamic_update_slice_in_dim(
        cache_k, k.astype(cache_k.dtype), 0, axis=1)
    cache_v = lax.dynamic_update_slice_in_dim(
        cache_v, v.astype(cache_v.dtype), 0, axis=1)
    if impl == "auto":
        impl = "naive" if s <= 2048 else "blocked"
    if impl == "blocked":
        out = _blocked_attention(q, k, v, positions, positions,
                                 cfg.sliding_window, scale,
                                 window_slice=window_slice)
    else:
        bias = _mask_bias(positions, positions, cfg.sliding_window)
        out = _sdpa(q, k, v, bias, scale)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return y, cache_k, cache_v


def attention_decode_ring(params: Params, cfg: ModelConfig, x: jax.Array,
                          cache_k: jax.Array, cache_v: jax.Array,
                          cache_index: jax.Array
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode against a RING (rolling) KV cache of length
    window+1 (§Perf: sliding-window archs keep O(window) state instead of
    O(seq_len); Mistral-style rolling buffer).

    Slot j holds absolute position  p(j) = index - ((index - j) mod L),
    L = cache length; keys are stored post-RoPE so only the mask needs
    absolute positions.
    """
    b = x.shape[0]
    ring = cache_k.shape[1]
    positions = jnp.full((b, 1), cache_index, dtype=jnp.int32)
    q, k_new, v_new = _qkv(params, cfg, x, positions)
    slot = jnp.mod(cache_index, ring)
    cache_k = lax.dynamic_update_slice_in_dim(
        cache_k, k_new.astype(cache_k.dtype), slot, axis=1)
    cache_v = lax.dynamic_update_slice_in_dim(
        cache_v, v_new.astype(cache_v.dtype), slot, axis=1)
    j = jnp.arange(ring)
    k_pos = cache_index - jnp.mod(cache_index - j, ring)
    valid = k_pos >= 0
    if cfg.sliding_window > 0:
        valid &= k_pos > (cache_index - cfg.sliding_window)
    valid = valid | (j == slot)              # the fresh token is always live
    bias = jnp.where(valid, 0.0, -1e30).astype(jnp.float32)[None, :]
    scale = cfg.attn_scale
    out = _sdpa(q, cache_k.astype(q.dtype), cache_v.astype(q.dtype),
                bias, scale)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return y, cache_k, cache_v


def attention_fill_ring(params: Params, cfg: ModelConfig, x: jax.Array,
                        positions: jax.Array, cache_k: jax.Array,
                        cache_v: jax.Array, impl: str = "auto",
                        window_slice: bool = False
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Prefill that fills a ring cache: only the last ``ring`` positions
    land in the buffer, at slot = position mod ring."""
    q, k, v = _qkv(params, cfg, x, positions)
    s = x.shape[1]
    ring = cache_k.shape[1]
    n = min(s, ring)
    tail_pos = jnp.arange(s - n, s)
    slots = jnp.mod(tail_pos, ring)
    cache_k = cache_k.at[:, slots].set(k[:, -n:].astype(cache_k.dtype))
    cache_v = cache_v.at[:, slots].set(v[:, -n:].astype(cache_v.dtype))
    scale = cfg.attn_scale
    if impl == "auto":
        impl = "naive" if s <= 2048 else "blocked"
    if impl == "blocked":
        out = _blocked_attention(q, k, v, positions, positions,
                                 cfg.sliding_window, scale,
                                 window_slice=window_slice)
    else:
        bias = _mask_bias(positions, positions, cfg.sliding_window)
        out = _sdpa(q, k, v, bias, scale)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return y, cache_k, cache_v


def attention_decode(params: Params, cfg: ModelConfig, x: jax.Array,
                     cache_k: jax.Array, cache_v: jax.Array,
                     cache_index: jax.Array, window_slice: bool = False
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode against a KV cache.

    x: [B, 1, d]; cache_k/v: [B, S_max, KV, D]; cache_index: scalar int32
    (current length, == position of the new token).
    Returns (y [B,1,d], new_cache_k, new_cache_v).

    ``window_slice``: with sliding-window attention active, read only a
    window-sized dynamic slice of the cache instead of masking the full
    S_max — turns decode HBM traffic from O(S_max) into O(window)
    (EXPERIMENTS.md §Perf; numerically identical, tested).
    """
    b, _, _ = x.shape
    s_max = cache_k.shape[1]
    positions = jnp.full((b, 1), cache_index, dtype=jnp.int32)
    q, k_new, v_new = _qkv(params, cfg, x, positions)
    cache_k = lax.dynamic_update_slice_in_dim(
        cache_k, k_new.astype(cache_k.dtype), cache_index, axis=1)
    cache_v = lax.dynamic_update_slice_in_dim(
        cache_v, v_new.astype(cache_v.dtype), cache_index, axis=1)
    scale = cfg.attn_scale
    win = cfg.sliding_window
    if window_slice and 0 < win < s_max:
        span = win + 1                     # window ending at the new token
        start = jnp.clip(cache_index - win, 0, s_max - span)
        k_r = lax.dynamic_slice_in_dim(cache_k, start, span, axis=1)
        v_r = lax.dynamic_slice_in_dim(cache_v, start, span, axis=1)
        k_pos = start + jnp.arange(span)
    else:
        k_r, v_r = cache_k, cache_v
        k_pos = jnp.arange(s_max)
    valid = k_pos <= cache_index
    if win > 0:
        valid &= k_pos > (cache_index - win)
    bias = jnp.where(valid, 0.0, -1e30).astype(jnp.float32)[None, :]
    out = _sdpa(q, k_r.astype(q.dtype), v_r.astype(q.dtype), bias, scale)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return y, cache_k, cache_v


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------


def init_mlp(key, d: int, f: int) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "norm": init_rms_norm(d),
        "wi_gate": dense_init(ks[0], (d, f)),
        "wi_up": dense_init(ks[1], (d, f)),
        "wo": dense_init(ks[2], (f, d)),
    }


def _act(name: str, x: jax.Array) -> jax.Array:
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def mlp(params: Params, x: jax.Array, act_fn: str = "silu") -> jax.Array:
    dtype = x.dtype
    gate = _act(act_fn, x @ params["wi_gate"].astype(dtype))
    up = x @ params["wi_up"].astype(dtype)
    return (gate * up) @ params["wo"].astype(dtype)
