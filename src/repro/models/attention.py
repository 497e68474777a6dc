"""Attention: GQA projections + three interchangeable inner implementations.

impl="reference"  — full (B,H,Lq,Lkv) score materialization (oracle, tests)
impl="blocked"    — jnp online-softmax over kv chunks (flash semantics,
                    compact HLO: what the dry-run lowers and what XLA:TPU
                    fuses well; differentiable via scan)
impl="flash"      — the Pallas kernel (TPU; interpret=True elsewhere)

GQA is computed grouped — q reshaped to (B, Hkv, G, L, D) — so kv is never
materialized per q-head.  Decode attends through repro.kernels.decode_attention
(or its ref), with uniform cache length per batch and flash-decoding LSE
output for sequence-sharded caches.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels.decode_attention.ops import decode_attention
from ..kernels.decode_attention.ref import decode_attention_ref
from ..kernels.flash_attention.ops import flash_attention
from .layers import Params, apply_rope, dense, dense_init

NEG_INF = -1e30


def init_attention(key, cfg: ModelConfig, dtype,
                   d_in: Optional[int] = None) -> Params:
    """q/k/v read ``d_in`` channels (default ``d_model``); o writes
    ``d_model``."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    d_in = d_in or d
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d_in, cfg.n_heads * hd, dtype,
                         bias=cfg.qkv_bias),
        "wk": dense_init(ks[1], d_in, cfg.n_kv_heads * hd, dtype,
                         bias=cfg.qkv_bias),
        "wv": dense_init(ks[2], d_in, cfg.n_kv_heads * hd, dtype,
                         bias=cfg.qkv_bias),
        "wo": dense_init(ks[3], cfg.n_heads * hd, d, dtype, bias=False),
    }


def _split_heads(x: jax.Array, n_heads: int) -> jax.Array:
    b, l, _ = x.shape
    return x.reshape(b, l, n_heads, -1).transpose(0, 2, 1, 3)   # (B,H,L,D)


def _merge_heads(x: jax.Array) -> jax.Array:
    b, h, l, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, l, h * d)


# --------------------------------------------------------- inner attention

def _reference_attn(q, k, v, causal: bool, q_offset: int, scale: float):
    b, hq, lq, d = q.shape
    hkv, lkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, lq, d).astype(jnp.float32)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k.astype(jnp.float32)) * scale
    if causal:
        qi = jnp.arange(lq)[:, None] + q_offset
        ki = jnp.arange(lkv)[None, :]
        s = jnp.where(ki <= qi, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, lq, d).astype(q.dtype)


def _blocked_attn(q, k, v, causal: bool, q_offset: int, scale: float,
                  chunk: int):
    """Online-softmax over kv chunks: flash semantics in pure jnp."""
    b, hq, lq, d = q.shape
    hkv, lkv = k.shape[1], k.shape[2]
    g = hq // hkv
    pad = (-lkv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    steps = (lkv + pad) // chunk
    kc = k.reshape(b, hkv, steps, chunk, d).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, hkv, steps, chunk, d).transpose(2, 0, 1, 3, 4)
    qg = q.reshape(b, hkv, g, lq, d).astype(jnp.float32)
    qi = jnp.arange(lq)[:, None] + q_offset                  # (Lq, 1)

    def step(carry, inp):
        m, l, acc = carry
        ic, kci, vci = inp
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg,
                       kci.astype(jnp.float32)) * scale
        ki = ic * chunk + jnp.arange(chunk)                  # (C,)
        if causal:
            valid = (ki[None, :] <= qi) & (ki[None, :] < lkv)  # (Lq, C)
            s = jnp.where(valid[None, None, None], s, NEG_INF)
        else:
            valid = ki < lkv                                 # (C,)
            s = jnp.where(valid[None, None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, vci.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((b, hkv, g, lq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, lq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, lq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (jnp.arange(steps), kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, hq, lq, d).astype(q.dtype)


def _inner_attention(cfg: ModelConfig, q, k, v, causal: bool, q_offset: int,
                     scale: Optional[float] = None):
    scale = scale or cfg.resolved_head_dim ** -0.5
    if cfg.attn_impl == "reference":
        return _reference_attn(q, k, v, causal, q_offset, scale)
    if cfg.attn_impl == "flash":
        interpret = jax.default_backend() != "tpu"
        return flash_attention(q, k, v, causal, scale, interpret)
    return _blocked_attn(q, k, v, causal, q_offset, scale, cfg.attn_chunk)


# ------------------------------------------------------------ public entry

def attend(cfg: ModelConfig, p: Params, x: jax.Array, positions: jax.Array,
           causal: bool = True,
           kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
           rope: bool = True, scale: Optional[float] = None) -> jax.Array:
    """Full-sequence attention (train / prefill). x: (B, L, D_in).
    ``scale`` overrides the softmax scale ``head_dim ** -0.5``."""
    hd = cfg.resolved_head_dim
    q = _split_heads(dense(p["wq"], x), cfg.n_heads)
    if kv_override is None:
        k = _split_heads(dense(p["wk"], x), cfg.n_kv_heads)
        v = _split_heads(dense(p["wv"], x), cfg.n_kv_heads)
    else:
        k, v = kv_override
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        if kv_override is None:
            k = apply_rope(k, positions, cfg.rope_theta)
    out = _inner_attention(cfg, q, k, v, causal, q_offset=0, scale=scale)
    return dense(p["wo"], _merge_heads(out))


def prefill_kv(cfg: ModelConfig, p: Params, x: jax.Array,
               positions: jax.Array, cache_size: int,
               rope: bool = True) -> Dict[str, jax.Array]:
    """Projected+rotated kv for the cache, padded to cache_size."""
    k = _split_heads(dense(p["wk"], x), cfg.n_kv_heads)
    v = _split_heads(dense(p["wv"], x), cfg.n_kv_heads)
    if rope:
        k = apply_rope(k, positions, cfg.rope_theta)
    pad = cache_size - k.shape[2]
    k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return {"k": k, "v": v}


def decode_attend(cfg: ModelConfig, p: Params, x: jax.Array,
                  cache: Dict[str, jax.Array], cache_len: jax.Array,
                  rope: bool = True, update_cache: bool = True,
                  use_kernel: bool = False
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode step. x: (B, 1, D); cache k/v: (B, Hkv, S, hd);
    cache_len: scalar int32 (uniform valid prefix)."""
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    pos = jnp.full((b, 1), cache_len, jnp.int32)
    q = _split_heads(dense(p["wq"], x), cfg.n_heads)         # (B,Hq,1,hd)
    k_new = _split_heads(dense(p["wk"], x), cfg.n_kv_heads)  # (B,Hkv,1,hd)
    v_new = _split_heads(dense(p["wv"], x), cfg.n_kv_heads)
    if rope:
        q = apply_rope(q, pos, cfg.rope_theta)
        k_new = apply_rope(k_new, pos, cfg.rope_theta)

    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, cache_len, axis=2)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, cache_len, axis=2)
    lens = jnp.full((b,), cache_len + 1, jnp.int32)

    qd = q[:, :, 0]                                          # (B,Hq,hd)
    if use_kernel:
        out = decode_attention(qd, k, v, lens,
                               interpret=jax.default_backend() != "tpu")
    else:
        out = decode_attention_ref(qd, k, v, lens)
    out = dense(p["wo"], out.reshape(b, 1, -1))
    new_cache = {"k": k, "v": v} if update_cache else cache
    return out, new_cache


# Stacked caches (N, S, B, Hkv, W): one per call of a shared block, the
# sequence major and the head dim padded to W, a multiple of 128 lanes.
# That is the layout the TPU keeps them in both at the jit boundary and
# inside the layer loop, so a decode step writes one position in place
# and never copies a whole cache to change its layout.

def stacked_width(head_dim: int) -> int:
    return -(-head_dim // 128) * 128


def stacked_kv(k: jax.Array, v: jax.Array, cache_size: int
               ) -> Dict[str, jax.Array]:
    """(B, Hkv, L, hd) k and v -> one entry of stacked caches,
    (cache_size, B, Hkv, W), zero beyond L and hd."""
    def put(t):
        t = t.transpose(2, 0, 1, 3)
        return jnp.pad(t, ((0, cache_size - t.shape[0]), (0, 0), (0, 0),
                           (0, stacked_width(t.shape[-1]) - t.shape[-1])))
    return {"k": put(k), "v": put(v)}


def decode_attend_stacked(cfg: ModelConfig, p: Params, x: jax.Array,
                          cache: Dict[str, jax.Array], index: jax.Array,
                          cache_len: jax.Array, scale: Optional[float] = None
                          ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode step into entry ``index`` of stacked caches: only
    the new position is written, in place.  x: (B, 1, D_in)."""
    b, hd = x.shape[0], cfg.resolved_head_dim
    hkv = cfg.n_kv_heads
    pos = jnp.full((b, 1), cache_len, jnp.int32)

    def heads(w, n):
        # split in float32: a bfloat16 split into heads of a width that is
        # not a multiple of 128 makes the compiler copy the whole weight
        # into a transposed layout every step
        return dense(w, x[:, 0]).astype(jnp.float32).reshape(b, n, 1, hd)
    q = apply_rope(heads(p["wq"], cfg.n_heads), pos,
                   cfg.rope_theta)[:, :, 0]                  # (B, Hq, hd)
    k = apply_rope(heads(p["wk"], hkv), pos, cfg.rope_theta)
    v = heads(p["wv"], hkv)
    new = stacked_kv(k, v, 1)                                # (1, B, Hkv, W)
    zero = jnp.int32(0)
    cache = {n: jax.lax.dynamic_update_slice(
        cache[n], new[n][None].astype(cache[n].dtype),
        (index, cache_len, zero, zero, zero)) for n in ("k", "v")}
    w = cache["k"].shape[-1]
    qg = jnp.pad(q.reshape(b, hkv, -1, hd), ((0, 0),) * 3 + ((0, w - hd),))
    s = jnp.einsum("bkgd,sbkd->bkgs", qg, cache["k"][index],
                   preferred_element_type=jnp.float32) * (scale or hd ** -0.5)
    s = jnp.where(jnp.arange(s.shape[-1]) <= cache_len, s, NEG_INF)
    out = jnp.einsum("bkgs,sbkd->bkgd", jax.nn.softmax(s, axis=-1),
                     cache["v"][index], preferred_element_type=jnp.float32)
    out = out[..., :hd].reshape(b, 1, -1).astype(x.dtype)
    return dense(p["wo"], out), cache
