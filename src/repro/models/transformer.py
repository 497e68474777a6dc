"""Model assembly for all assigned families.

Families:
  dense / vlm     — decoder-only GQA transformer (+ optional early-fusion
                    patch embeddings, frontend STUB)
  moe             — dense attention + (interleaved) MoE FFN
  mamba_hybrid    — zamba2: mamba2 backbone, two weight-SHARED attention
                    blocks called at the listed hybrid layers (one KV cache,
                    LoRA and output linear per call)
  rwkv            — RWKV6 time-mix + channel-mix
  encdec          — whisper: bidirectional encoder (stub audio frames) +
                    causal decoder with cross-attention

All stacks scan over layers (stacked params) so 88-layer models lower as a
single-layer HLO body — this is what keeps 80 dry-run compiles feasible and
is also the production choice (compile time, code size on device).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import attention as attn
from . import mamba2 as m2
from . import moe as moe_mod
from . import runtime
from . import rwkv6 as r6
from .layers import (Params, dense, dense_init, embed_init, gelu, layernorm,
                     layernorm_init, rmsnorm, rmsnorm_init, swiglu)


def _dtype(cfg: ModelConfig):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]


def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def _stack_init(key, n: int, fn):
    return jax.vmap(fn)(jax.random.split(key, n))


# =====================================================================
# shared layer pieces
# =====================================================================

def _init_mlp(key, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(key, 3)
    return {"gate": dense_init(ks[0], cfg.d_model, cfg.d_ff, dtype),
            "up": dense_init(ks[1], cfg.d_model, cfg.d_ff, dtype),
            "down": dense_init(ks[2], cfg.d_ff, cfg.d_model, dtype)}


def _mlp(p: Params, x: jax.Array) -> jax.Array:
    return dense(p["down"], swiglu(dense(p["gate"], x), dense(p["up"], x)))


def _init_dense_layer(cfg: ModelConfig, dtype, use_moe: bool):
    def init(key):
        ks = jax.random.split(key, 3)
        p = {"ln1": rmsnorm_init(cfg.d_model, dtype),
             "ln2": rmsnorm_init(cfg.d_model, dtype),
             "attn": attn.init_attention(ks[0], cfg, dtype)}
        if use_moe:
            p["moe"] = moe_mod.init_moe(ks[1], cfg, dtype)
        else:
            p["mlp"] = _init_mlp(ks[2], cfg, dtype)
        return p
    return init


def _dense_layer_fwd(cfg: ModelConfig, p: Params, x, positions):
    h = attn.attend(cfg, p["attn"], rmsnorm(p["ln1"], x), positions)
    x = x + h
    y = rmsnorm(p["ln2"], x)
    y = moe_mod.moe_apply(cfg, p["moe"], y) if "moe" in p else _mlp(p["mlp"], y)
    return x + y


def _dense_layer_decode(cfg: ModelConfig, p: Params, x, cache, cache_len):
    h, new_cache = attn.decode_attend(cfg, p["attn"], rmsnorm(p["ln1"], x),
                                      cache, cache_len)
    x = x + h
    y = rmsnorm(p["ln2"], x)
    y = moe_mod.moe_apply(cfg, p["moe"], y) if "moe" in p else _mlp(p["mlp"], y)
    return x + y, new_cache


def _dense_layer_prefill(cfg: ModelConfig, p: Params, x, positions,
                         cache_size: int):
    xin = rmsnorm(p["ln1"], x)
    cache = attn.prefill_kv(cfg, p["attn"], xin, positions, cache_size)
    h = attn.attend(cfg, p["attn"], xin, positions)
    x = x + h
    y = rmsnorm(p["ln2"], x)
    y = moe_mod.moe_apply(cfg, p["moe"], y) if "moe" in p else _mlp(p["mlp"], y)
    return x + y, cache


# =====================================================================
# decoder-only (dense / vlm / moe)
# =====================================================================

def _moe_layout(cfg: ModelConfig) -> Tuple[int, int]:
    """(#scan steps, layers per step). moe_every=2 scans (dense, moe) pairs."""
    if cfg.family == "moe" and cfg.moe_every == 2:
        return cfg.n_layers // 2, 2
    return cfg.n_layers, 1


def init_decoder_params(cfg: ModelConfig, key) -> Params:
    dtype = _dtype(cfg)
    ks = jax.random.split(key, 6)
    p: Params = {"embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model, dtype),
                 "final_norm": rmsnorm_init(cfg.d_model, dtype)}
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[1], cfg.d_model, cfg.padded_vocab, dtype)

    steps, per = _moe_layout(cfg)
    if cfg.family == "moe":
        if per == 2:   # interleaved: scan body = dense layer + moe layer
            p["layers"] = _stack_init(
                ks[2], steps,
                lambda k: {"dense": _init_dense_layer(cfg, dtype, False)(
                               jax.random.fold_in(k, 0)),
                           "moe": _init_dense_layer(cfg, dtype, True)(
                               jax.random.fold_in(k, 1))})
        else:
            p["layers"] = _stack_init(ks[2], steps,
                                      _init_dense_layer(cfg, dtype, True))
    else:
        p["layers"] = _stack_init(ks[2], steps,
                                  _init_dense_layer(cfg, dtype, False))
    if cfg.frontend == "vit" and cfg.num_patches:
        p["patch_proj"] = dense_init(ks[3], cfg.frontend_dim, cfg.d_model,
                                     dtype)
    return p


def _embed_inputs(cfg: ModelConfig, params: Params, tokens: jax.Array,
                  patches: Optional[jax.Array]) -> jax.Array:
    x = params["embed"][tokens]
    if patches is not None and "patch_proj" in params:
        pe = dense(params["patch_proj"], patches.astype(x.dtype))
        x = jnp.concatenate([pe, x], axis=1)          # early fusion: prepend
    return x


def _mask_pad_vocab(cfg: ModelConfig, logits: jax.Array) -> jax.Array:
    """Vocab is padded to a 128-multiple for TP sharding; mask the pad."""
    if cfg.padded_vocab == cfg.vocab:
        return logits
    ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return jnp.where(ids < cfg.vocab, logits, -1e30)


def _logits(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        out = x.astype(jnp.float32) @ params["embed"].T.astype(jnp.float32)
    else:
        out = x.astype(jnp.float32) @ params["lm_head"]["w"].astype(jnp.float32)
    return _mask_pad_vocab(cfg, out)


def decoder_forward(cfg: ModelConfig, params: Params, tokens: jax.Array,
                    patches: Optional[jax.Array] = None) -> jax.Array:
    """Train/eval forward -> logits (B, L, V)."""
    x = _embed_inputs(cfg, params, tokens, patches)
    b, l, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32)[None], (b, l))

    steps, per = _moe_layout(cfg)

    def body(xc, lp):
        xc = runtime.constrain_batch(xc)
        if per == 2:
            xc = _dense_layer_fwd(cfg, lp["dense"], xc, positions)
            xc = _dense_layer_fwd(cfg, lp["moe"], xc, positions)
        else:
            xc = _dense_layer_fwd(cfg, lp, xc, positions)
        return xc, None

    if cfg.scan_layers:
        x, _ = jax.lax.scan(_remat(cfg, body), x, params["layers"])
    else:
        for i in range(steps):
            lp = jax.tree.map(lambda t: t[i], params["layers"])
            x, _ = body(x, lp)
    x = rmsnorm(params["final_norm"], x)
    return _logits(cfg, params, x)


def decoder_prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
                    cache_size: int, patches: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Prefill: logits of last position + per-layer kv caches (stacked)."""
    x = _embed_inputs(cfg, params, tokens, patches)
    b, l, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32)[None], (b, l))
    steps, per = _moe_layout(cfg)
    # sequence parallelism for non-MoE prefill (MoE dispatch shard_maps over
    # the batch layout; see runtime.constrain_seq docstring)
    seq_par = cfg.family != "moe"

    def body(xc, lp):
        xc = (runtime.constrain_seq(xc) if seq_par
              else runtime.constrain_batch(xc))
        if per == 2:
            xc, c1 = _dense_layer_prefill(cfg, lp["dense"], xc, positions,
                                          cache_size)
            xc, c2 = _dense_layer_prefill(cfg, lp["moe"], xc, positions,
                                          cache_size)
            return xc, {"dense": c1, "moe": c2}
        xc, c = _dense_layer_prefill(cfg, lp, xc, positions, cache_size)
        return xc, c

    if cfg.scan_layers:
        x, caches = jax.lax.scan(_remat(cfg, body), x, params["layers"])
    else:
        cs = []
        for i in range(steps):
            lp = jax.tree.map(lambda t: t[i], params["layers"])
            x, c = body(x, lp)
            cs.append(c)
        caches = jax.tree.map(lambda *t: jnp.stack(t), *cs)
    x = rmsnorm(params["final_norm"], x[:, -1:])
    state = {"cache": caches, "len": jnp.int32(l)}
    return _logits(cfg, params, x), state


def decoder_decode(cfg: ModelConfig, params: Params, tokens: jax.Array,
                   state: Dict[str, Any]
                   ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode step. tokens: (B, 1)."""
    x = params["embed"][tokens]
    cache_len = state["len"]
    steps, per = _moe_layout(cfg)

    def body(xc, inp):
        lp, cache = inp
        if per == 2:
            xc, c1 = _dense_layer_decode(cfg, lp["dense"], xc,
                                         cache["dense"], cache_len)
            xc, c2 = _dense_layer_decode(cfg, lp["moe"], xc,
                                         cache["moe"], cache_len)
            return xc, {"dense": c1, "moe": c2}
        xc, c = _dense_layer_decode(cfg, lp, xc, cache, cache_len)
        return xc, c

    if cfg.scan_layers:
        x, caches = jax.lax.scan(body, x, (params["layers"], state["cache"]))
    else:
        cs = []
        for i in range(steps):
            lp = jax.tree.map(lambda t: t[i], params["layers"])
            cache = jax.tree.map(lambda t: t[i], state["cache"])
            x, c = body(x, (lp, cache))
            cs.append(c)
        caches = jax.tree.map(lambda *t: jnp.stack(t), *cs)
    x = rmsnorm(params["final_norm"], x)
    return _logits(cfg, params, x), {"cache": caches, "len": cache_len + 1}


# =====================================================================
# zamba2: mamba2 backbone + shared attention blocks
# =====================================================================
#
# Each layer is a Mamba2 layer, ``h <- h + mamba(norm(h + t))``.  At the
# layers of ``cfg.hybrid_layers``, ``t`` is the output of a shared-block
# call (zero elsewhere): call j runs block ``j % shared_blocks`` on
# ``concat[h, e]`` (``e`` the token embedding) with call j's own MLP
# adapter and output linear, and keeps its own KV cache.  One scan runs
# every layer; a ``lax.cond`` on the layer's call index makes the call.

def _zamba_calls(cfg: ModelConfig) -> jax.Array:
    """Per layer: the index of the shared-block call before its mixer,
    or -1."""
    calls = [-1] * cfg.n_layers
    for j, layer in enumerate(cfg.hybrid_layers):
        calls[layer] = j
    return jnp.asarray(calls, jnp.int32)


def init_zamba_params(cfg: ModelConfig, key) -> Params:
    dtype = _dtype(cfg)
    d, ff, r = cfg.d_model, cfg.d_ff, cfg.adapter_rank
    ks = jax.random.split(key, 6)
    block = lambda k: {
        "ln_in": rmsnorm_init(2 * d, dtype),
        "attn": attn.init_attention(jax.random.fold_in(k, 0), cfg, dtype,
                                    d_in=2 * d),
        "ln_ff": rmsnorm_init(d, dtype),
        "gate_up": dense_init(jax.random.fold_in(k, 1), d, 2 * ff, dtype),
        "down": dense_init(jax.random.fold_in(k, 2), ff, d, dtype)}
    call = lambda k: {
        "adapter_in": dense_init(jax.random.fold_in(k, 0), d, r, dtype),
        "adapter_out": dense_init(jax.random.fold_in(k, 1), r, 2 * ff,
                                  dtype),
        "linear": dense_init(jax.random.fold_in(k, 2), d, d, dtype)}
    layer = lambda k: {"ln": rmsnorm_init(d, dtype),
                       "mamba": m2.init_mamba2(k, cfg, dtype)}
    p = {"embed": embed_init(ks[0], cfg.padded_vocab, d, dtype),
         "final_norm": rmsnorm_init(d, dtype),
         "layers": _stack_init(ks[1], cfg.n_layers, layer),
         "shared": _stack_init(ks[2], cfg.shared_blocks, block),
         "calls": _stack_init(ks[3], len(cfg.hybrid_layers), call)}
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[4], d, cfg.padded_vocab, dtype)
    return p


def _zamba_call_weights(cfg: ModelConfig, params: Params, j):
    """Call ``j``'s block (taken in turn) and its own adapter and linear."""
    sp = jax.tree.map(lambda w: w[j % cfg.shared_blocks], params["shared"])
    cp = jax.tree.map(lambda w: w[j], params["calls"])
    return sp, cp


def _zamba_block_in(cfg: ModelConfig, sp: Params, h, e):
    return rmsnorm(sp["ln_in"], jnp.concatenate([h, e], axis=-1),
                   cfg.norm_eps)


def _zamba_block_out(cfg: ModelConfig, sp: Params, cp: Params, a):
    """From the attention output: RMSNorm, the GELU MLP whose gate/up
    projection adds the call's LoRA, then the call's linear."""
    x = rmsnorm(sp["ln_ff"], a, cfg.norm_eps)
    gu = dense(sp["gate_up"], x) + dense(cp["adapter_out"],
                                         dense(cp["adapter_in"], x))
    g, u = jnp.split(gu, 2, axis=-1)
    g = jax.nn.gelu(g.astype(jnp.float32), approximate=False)
    return dense(cp["linear"], dense(sp["down"], g.astype(u.dtype) * u))


def _zamba_scale(cfg: ModelConfig) -> float:
    return (cfg.resolved_head_dim / 2) ** -0.5


def _zamba_mixer_in(cfg: ModelConfig, lp: Params, h, t):
    return rmsnorm(lp["ln"], h + t, cfg.norm_eps)


def zamba_forward(cfg: ModelConfig, params: Params, tokens: jax.Array
                  ) -> jax.Array:
    e = params["embed"][tokens]
    b, l, _ = e.shape
    positions = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32)[None], (b, l))

    def call(h, j):
        with jax.named_scope("zamba2.shared"):
            sp, cp = _zamba_call_weights(cfg, params, j)
            a = attn.attend(cfg, sp["attn"], _zamba_block_in(cfg, sp, h, e),
                            positions, scale=_zamba_scale(cfg))
            return _zamba_block_out(cfg, sp, cp, a)

    def body(h, inp):
        lp, j = inp
        h = runtime.constrain_batch(h)
        t = jax.lax.cond(j >= 0, call, lambda h, j: jnp.zeros_like(h), h, j)
        with jax.named_scope("zamba2.ssm"):
            y = m2.mamba2_forward(cfg, lp["mamba"],
                                  _zamba_mixer_in(cfg, lp, h, t))
        return h + y, None

    h, _ = jax.lax.scan(_remat(cfg, body), e,
                        (params["layers"], _zamba_calls(cfg)))
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return _logits(cfg, params, h)


def zamba_init_state(cfg: ModelConfig, batch: int, cache_size: int,
                     dtype) -> Dict[str, Any]:
    """KV caches per shared-block call (``attention.stacked_kv``'s
    layout), conv and SSM state per layer."""
    kv = (len(cfg.hybrid_layers), cache_size, batch, cfg.n_kv_heads,
          attn.stacked_width(cfg.resolved_head_dim))
    mamba = jax.tree.map(
        lambda t: jnp.broadcast_to(t, (cfg.n_layers,) + t.shape),
        m2.mamba2_init_cache(cfg, batch, dtype))
    return {"kv": {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)},
            "mamba": mamba, "len": jnp.int32(0)}


def zamba_prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
                  cache_size: int) -> Tuple[jax.Array, Dict[str, Any]]:
    """Full-sequence hybrid prefill: chunked-scan mamba layers that keep
    their final state, and each call's KV written into its cache."""
    e = params["embed"][tokens]
    b, l, _ = e.shape
    positions = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32)[None], (b, l))
    kv0 = zamba_init_state(cfg, b, cache_size, e.dtype)["kv"]

    def call(h, j, kv):
        with jax.named_scope("zamba2.shared"):
            sp, cp = _zamba_call_weights(cfg, params, j)
            xin = _zamba_block_in(cfg, sp, h, e)
            k, v = (attn.prefill_kv(cfg, sp["attn"], xin, positions, l)[n]
                    for n in ("k", "v"))
            new = attn.stacked_kv(k, v, cache_size)
            kv = {n: jax.lax.dynamic_update_index_in_dim(kv[n], new[n], j, 0)
                  for n in ("k", "v")}
            a = attn.attend(cfg, sp["attn"], xin, positions,
                            kv_override=(k, v), scale=_zamba_scale(cfg))
            return _zamba_block_out(cfg, sp, cp, a), kv

    def body(carry, inp):
        h, kv = carry
        lp, j = inp
        t, kv = jax.lax.cond(j >= 0, call,
                             lambda h, j, kv: (jnp.zeros_like(h), kv),
                             h, j, kv)
        with jax.named_scope("zamba2.ssm"):
            y, mc = m2.mamba2_forward(cfg, lp["mamba"],
                                      _zamba_mixer_in(cfg, lp, h, t),
                                      collect=True)
        return (h + y, kv), mc

    (h, kv), mamba = jax.lax.scan(_remat(cfg, body), (e, kv0),
                                  (params["layers"], _zamba_calls(cfg)))
    h = rmsnorm(params["final_norm"], h[:, -1:], cfg.norm_eps)
    state = {"kv": kv, "mamba": mamba, "len": jnp.int32(l)}
    return _logits(cfg, params, h), state


def zamba_decode(cfg: ModelConfig, params: Params, tokens: jax.Array,
                 state: Dict[str, Any]) -> Tuple[jax.Array, Dict[str, Any]]:
    e = params["embed"][tokens]
    cache_len = state["len"]

    def call(h, j, kv):
        with jax.named_scope("zamba2.shared"):
            sp, cp = _zamba_call_weights(cfg, params, j)
            a, kv = attn.decode_attend_stacked(
                cfg, sp["attn"], _zamba_block_in(cfg, sp, h, e), kv, j,
                cache_len, scale=_zamba_scale(cfg))
            return _zamba_block_out(cfg, sp, cp, a), kv

    def body(carry, inp):
        h, kv = carry
        lp, j, mc = inp
        t, kv = jax.lax.cond(j >= 0, call,
                             lambda h, j, kv: (jnp.zeros_like(h), kv),
                             h, j, kv)
        with jax.named_scope("zamba2.ssm"):
            y, mc = m2.mamba2_decode(cfg, lp["mamba"],
                                     _zamba_mixer_in(cfg, lp, h, t), mc)
        return (h + y, kv), mc

    (h, kv), mamba = jax.lax.scan(
        body, (e, state["kv"]),
        (params["layers"], _zamba_calls(cfg), state["mamba"]))
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return _logits(cfg, params, h), {"kv": kv, "mamba": mamba,
                                     "len": cache_len + 1}


# =====================================================================
# rwkv6
# =====================================================================

def init_rwkv_params(cfg: ModelConfig, key) -> Params:
    dtype = _dtype(cfg)
    ks = jax.random.split(key, 4)
    layer_init = lambda k: {
        "ln1": layernorm_init(cfg.d_model, dtype),
        "time": r6.init_rwkv6_time(jax.random.fold_in(k, 0), cfg, dtype),
        "ln2": layernorm_init(cfg.d_model, dtype),
        "chan": r6.init_rwkv6_channel(jax.random.fold_in(k, 1), cfg, dtype),
    }
    return {"embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model, dtype),
            "ln_in": layernorm_init(cfg.d_model, dtype),
            "final_norm": layernorm_init(cfg.d_model, dtype),
            "lm_head": dense_init(ks[1], cfg.d_model, cfg.padded_vocab, dtype),
            "layers": _stack_init(ks[2], cfg.n_layers, layer_init)}


def rwkv_init_state(cfg: ModelConfig, batch: int, dtype) -> Dict[str, Any]:
    hd = cfg.resolved_head_dim
    l = cfg.n_layers
    return {
        "time_x": jnp.zeros((l, batch, 1, cfg.d_model), dtype),
        "wkv": jnp.zeros((l, batch, cfg.n_heads, hd, hd), jnp.float32),
        "chan_x": jnp.zeros((l, batch, 1, cfg.d_model), dtype),
        "len": jnp.int32(0),
    }


def rwkv_forward(cfg: ModelConfig, params: Params, tokens: jax.Array,
                 state: Optional[Dict[str, Any]] = None, collect: bool = False):
    """Full-sequence forward; optionally threads/returns recurrent state."""
    x = layernorm(params["ln_in"], params["embed"][tokens])
    b = x.shape[0]
    if state is None:
        state = rwkv_init_state(cfg, b, x.dtype)

    def body(xc, inp):
        xc = runtime.constrain_batch(xc)
        lp, tx, wkv, cx = inp
        h, ntx, nwkv = r6.rwkv6_time_mix(cfg, lp["time"],
                                         layernorm(lp["ln1"], xc), tx, wkv)
        xc = xc + h
        h, ncx = r6.rwkv6_channel_mix(cfg, lp["chan"],
                                      layernorm(lp["ln2"], xc), cx)
        return xc + h, (ntx, nwkv, ncx)

    x, (ntx, nwkv, ncx) = jax.lax.scan(
        _remat(cfg, body), x,
        (params["layers"], state["time_x"], state["wkv"], state["chan_x"]))
    x = layernorm(params["final_norm"], x)
    logits = _logits(cfg, params, x)
    if collect:
        new_state = {"time_x": ntx, "wkv": nwkv, "chan_x": ncx,
                     "len": state["len"] + tokens.shape[1]}
        return logits, new_state
    return logits


def rwkv_decode(cfg: ModelConfig, params: Params, tokens: jax.Array,
                state: Dict[str, Any]) -> Tuple[jax.Array, Dict[str, Any]]:
    logits, new_state = rwkv_forward(cfg, params, tokens, state, collect=True)
    return logits, new_state


# =====================================================================
# whisper (enc-dec)
# =====================================================================

def _sinusoidal(l: int, d: int) -> jax.Array:
    pos = jnp.arange(l, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    freq = jnp.exp(-jnp.log(10000.0) * dim / (d // 2))
    ang = pos * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def init_encdec_params(cfg: ModelConfig, key) -> Params:
    dtype = _dtype(cfg)
    ks = jax.random.split(key, 6)
    enc_init = lambda k: {
        "ln1": layernorm_init(cfg.d_model, dtype),
        "attn": attn.init_attention(jax.random.fold_in(k, 0), cfg, dtype),
        "ln2": layernorm_init(cfg.d_model, dtype),
        "mlp": {"up": dense_init(jax.random.fold_in(k, 1), cfg.d_model,
                                 cfg.d_ff, dtype, bias=True),
                "down": dense_init(jax.random.fold_in(k, 2), cfg.d_ff,
                                   cfg.d_model, dtype, bias=True)}}
    dec_init = lambda k: {
        "ln1": layernorm_init(cfg.d_model, dtype),
        "self": attn.init_attention(jax.random.fold_in(k, 0), cfg, dtype),
        "ln_x": layernorm_init(cfg.d_model, dtype),
        "cross": attn.init_attention(jax.random.fold_in(k, 1), cfg, dtype),
        "ln2": layernorm_init(cfg.d_model, dtype),
        "mlp": {"up": dense_init(jax.random.fold_in(k, 2), cfg.d_model,
                                 cfg.d_ff, dtype, bias=True),
                "down": dense_init(jax.random.fold_in(k, 3), cfg.d_ff,
                                   cfg.d_model, dtype, bias=True)}}
    return {"embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model, dtype),
            "enc_layers": _stack_init(ks[1], cfg.enc_layers, enc_init),
            "enc_norm": layernorm_init(cfg.d_model, dtype),
            "dec_layers": _stack_init(ks[2], cfg.dec_layers, dec_init),
            "dec_norm": layernorm_init(cfg.d_model, dtype)}


def _ff(p, x):
    return dense(p["down"], gelu(dense(p["up"], x)))


def encode(cfg: ModelConfig, params: Params, frames: jax.Array) -> jax.Array:
    """frames: (B, T, D) stubbed conv-frontend output."""
    b, t, d = frames.shape
    dtype = params["enc_norm"]["scale"].dtype    # model compute dtype
    frames = frames.astype(dtype)
    x = frames + _sinusoidal(t, d).astype(dtype)[None]
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))

    def body(xc, lp):
        xc = runtime.constrain_batch(xc)
        h = attn.attend(cfg, lp["attn"], layernorm(lp["ln1"], xc), positions,
                        causal=False, rope=False)
        xc = xc + h
        return xc + _ff(lp["mlp"], layernorm(lp["ln2"], xc)), None

    x, _ = jax.lax.scan(_remat(cfg, body), x, params["enc_layers"])
    return layernorm(params["enc_norm"], x)


def encdec_forward(cfg: ModelConfig, params: Params, tokens: jax.Array,
                   frames: jax.Array) -> jax.Array:
    enc = encode(cfg, params, frames)
    b, l = tokens.shape
    x = params["embed"][tokens] + _sinusoidal(l, cfg.d_model).astype(
        params["embed"].dtype)[None]
    positions = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32)[None], (b, l))

    def body(xc, lp):
        xc = runtime.constrain_seq(xc)
        h = attn.attend(cfg, lp["self"], layernorm(lp["ln1"], xc), positions,
                        causal=True, rope=False)
        xc = xc + h
        # cross attention: kv from encoder output
        kx = layernorm(lp["ln_x"], xc)
        k = dense(lp["cross"]["wk"], enc).reshape(
            b, enc.shape[1], cfg.n_kv_heads, -1).transpose(0, 2, 1, 3)
        v = dense(lp["cross"]["wv"], enc).reshape(
            b, enc.shape[1], cfg.n_kv_heads, -1).transpose(0, 2, 1, 3)
        h = attn.attend(cfg, lp["cross"], kx, positions, causal=False,
                        kv_override=(k, v), rope=False)
        xc = xc + h
        return xc + _ff(lp["mlp"], layernorm(lp["ln2"], xc)), None

    x, _ = jax.lax.scan(_remat(cfg, body), x, params["dec_layers"])
    x = layernorm(params["dec_norm"], x)
    return _mask_pad_vocab(
        cfg, x.astype(jnp.float32) @ params["embed"].T.astype(jnp.float32))


def encdec_init_state(cfg: ModelConfig, params: Params, frames: jax.Array,
                      cache_size: int) -> Dict[str, Any]:
    """Precompute encoder output + cross-kv; empty self-attn caches."""
    enc = encode(cfg, params, frames)
    b = enc.shape[0]
    hd = cfg.resolved_head_dim

    def cross_kv(lp):
        k = dense(lp["cross"]["wk"], enc).reshape(
            b, enc.shape[1], cfg.n_kv_heads, -1).transpose(0, 2, 1, 3)
        v = dense(lp["cross"]["wv"], enc).reshape(
            b, enc.shape[1], cfg.n_kv_heads, -1).transpose(0, 2, 1, 3)
        return {"k": k, "v": v}

    cross = jax.tree.map(lambda *t: jnp.stack(t),
                         *[cross_kv(jax.tree.map(lambda q: q[i],
                                                 params["dec_layers"]))
                           for i in range(cfg.dec_layers)])
    selfc = {"k": jnp.zeros((cfg.dec_layers, b, cfg.n_kv_heads, cache_size,
                             hd), enc.dtype),
             "v": jnp.zeros((cfg.dec_layers, b, cfg.n_kv_heads, cache_size,
                             hd), enc.dtype)}
    return {"cross": cross, "self": selfc, "len": jnp.int32(0)}


def encdec_prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
                   frames: jax.Array, cache_size: int
                   ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Run the decoder over the whole prompt, capturing self-attn kv."""
    state = encdec_init_state(cfg, params, frames, cache_size)
    enc = encode(cfg, params, frames)
    b, l = tokens.shape
    x = params["embed"][tokens] + _sinusoidal(l, cfg.d_model).astype(
        params["embed"].dtype)[None]
    positions = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32)[None], (b, l))

    def body(xc, lp):
        xc = runtime.constrain_seq(xc)
        xin = layernorm(lp["ln1"], xc)
        cache = attn.prefill_kv(cfg, lp["self"], xin, positions, cache_size,
                                rope=False)
        h = attn.attend(cfg, lp["self"], xin, positions, causal=True,
                        rope=False)
        xc = xc + h
        kx = layernorm(lp["ln_x"], xc)
        k = dense(lp["cross"]["wk"], enc).reshape(
            b, enc.shape[1], cfg.n_kv_heads, -1).transpose(0, 2, 1, 3)
        v = dense(lp["cross"]["wv"], enc).reshape(
            b, enc.shape[1], cfg.n_kv_heads, -1).transpose(0, 2, 1, 3)
        h = attn.attend(cfg, lp["cross"], kx, positions, causal=False,
                        kv_override=(k, v), rope=False)
        xc = xc + h
        return xc + _ff(lp["mlp"], layernorm(lp["ln2"], xc)), cache

    x, selfc = jax.lax.scan(_remat(cfg, body), x, params["dec_layers"])
    x = layernorm(params["dec_norm"], x[:, -1:])
    logits = _mask_pad_vocab(
        cfg, x.astype(jnp.float32) @ params["embed"].T.astype(jnp.float32))
    return logits, {"cross": state["cross"], "self": selfc,
                    "len": jnp.int32(l)}


def encdec_decode(cfg: ModelConfig, params: Params, tokens: jax.Array,
                  state: Dict[str, Any]) -> Tuple[jax.Array, Dict[str, Any]]:
    b = tokens.shape[0]
    cache_len = state["len"]
    # sinusoidal position embedding at the (traced) decode position
    d = cfg.d_model
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    freq = jnp.exp(-jnp.log(10000.0) * dim / (d // 2))
    ang = cache_len.astype(jnp.float32) * freq
    pos_emb = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    x = params["embed"][tokens] + pos_emb.astype(params["embed"].dtype)[None]

    def body(xc, inp):
        lp, sc, xc_kv = inp
        h, nsc = attn.decode_attend(cfg, lp["self"],
                                    layernorm(lp["ln1"], xc), sc, cache_len,
                                    rope=False)
        xc = xc + h
        kx = layernorm(lp["ln_x"], xc)
        enc_len = jnp.full((b,), xc_kv["k"].shape[2], jnp.int32)
        from ..kernels.decode_attention.ref import decode_attention_ref
        q = dense(lp["cross"]["wq"], kx).reshape(b, cfg.n_heads, -1)
        o = decode_attention_ref(q, xc_kv["k"], xc_kv["v"], enc_len)
        xc = xc + dense(lp["cross"]["wo"], o.reshape(b, 1, -1))
        return xc + _ff(lp["mlp"], layernorm(lp["ln2"], xc)), nsc

    x, nself = jax.lax.scan(body, x,
                            (params["dec_layers"], state["self"],
                             state["cross"]))
    x = layernorm(params["dec_norm"], x)
    logits = _mask_pad_vocab(
        cfg, x.astype(jnp.float32) @ params["embed"].T.astype(jnp.float32))
    return logits, {"cross": state["cross"], "self": nself,
                    "len": cache_len + 1}
