"""Mamba2 (SSD) block — zamba2's backbone layer.

Structure per Mamba2: in_proj -> [z | x | B | C | dt]; causal depthwise
conv over (x,B,C); dt = softplus(dt + bias); per-head scalar decay
g = dt * (-exp(A_log)); SSD recurrence via the shared linear_scan kernel
(inclusive: y_t = C_t . h_t) with k=B_t, v=dt*x_t; skip D*x; gated RMSNorm
with the gate first, ``norm(y * silu(z))``, normalised over each group's
``d_inner / ssm_groups`` channels; out_proj.  B and C come in
``ssm_groups`` groups: head h reads group ``h // (heads / groups)``.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels.linear_scan.ops import linear_scan
from ..kernels.linear_scan.ref import linear_scan_chunked
from .layers import Params, dense, dense_init, rmsnorm_init


def init_mamba2(key, cfg: ModelConfig, dtype) -> Params:
    d, di, gn = cfg.d_model, cfg.d_inner, cfg.ssm_groups * cfg.ssm_state
    h = cfg.ssm_heads
    conv_ch = di + 2 * gn
    ks = jax.random.split(key, 5)
    return {
        "in_proj": dense_init(ks[0], d, 2 * di + 2 * gn + h, dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv, conv_ch),
                                     jnp.float32) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "a_log": jnp.zeros((h,), jnp.float32),            # A = -exp(a_log)
        "dt_bias": jnp.full((h,), -1.0, jnp.float32),     # softplus bias
        "d_skip": jnp.ones((h,), jnp.float32),
        "norm": rmsnorm_init(di, dtype),
        "out_proj": dense_init(ks[2], di, d, dtype),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 window: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal conv. x: (B, L, C); w: (K, C).
    window: (B, K-1, C) carried context for decode (None -> zero history)."""
    k = w.shape[0]
    if window is None:
        window = jnp.zeros((x.shape[0], k - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([window, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k))
    return jax.nn.silu((out + b).astype(jnp.float32)).astype(x.dtype)


def _ssd_inputs(cfg: ModelConfig, p: Params, x: jax.Array):
    di, gn = cfg.d_inner, cfg.ssm_groups * cfg.ssm_state
    proj = dense(p["in_proj"], x)                          # (B, L, ...)
    z, xbc, dt_raw = jnp.split(proj, [di, 2 * di + 2 * gn], axis=-1)
    return z, xbc, dt_raw


def _ssd_core(cfg, p, xbc_conv, dt_raw):
    """Split conv output, build SSD tensors: q, k (B,H,L,N), v (B,H,L,P)
    and the per-head scalar log decay g (B,H,L,1)."""
    b, l, _ = xbc_conv.shape
    di, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    grp = cfg.ssm_groups
    hp = cfg.ssm_head_dim
    xs, bmat, cmat = jnp.split(xbc_conv, [di, di + grp * n], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,L,H)
    a = -jnp.exp(p["a_log"])                               # (H,)
    g = (dt * a)[..., None]                                # (B,L,H,1) log decay
    xh = xs.reshape(b, l, h, hp)
    v = (xh.astype(jnp.float32) * dt[..., None]).astype(xs.dtype)
    per_head = lambda t: jnp.repeat(t.reshape(b, l, grp, n), h // grp,
                                    axis=2)                # (B,L,H,N)
    to_bhl = lambda t: t.transpose(0, 2, 1, 3)             # (B,H,L,*)
    return (to_bhl(per_head(cmat)), to_bhl(per_head(bmat)), to_bhl(v),
            to_bhl(g), xh, dt)


def _gated_norm(cfg: ModelConfig, p: Params, y: jax.Array, z: jax.Array
                ) -> jax.Array:
    """``norm(y * silu(z))``: the gate first, then RMS over each group of
    ``d_inner / ssm_groups`` channels, then the scale."""
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    yg = yf.reshape(*yf.shape[:-1], cfg.ssm_groups, -1)
    var = jnp.mean(yg * yg, axis=-1, keepdims=True)
    yn = (yg * jax.lax.rsqrt(var + cfg.norm_eps)).reshape(yf.shape)
    return yn.astype(y.dtype) * p["norm"]["scale"]


def mamba2_forward(cfg: ModelConfig, p: Params, x: jax.Array,
                   use_kernel: bool = False, collect: bool = False):
    """Full-sequence forward. x: (B, L, d_model).
    collect=True also returns the decode cache (conv window + final state)."""
    z, xbc_in, dt_raw = _ssd_inputs(cfg, p, x)
    xbc = _causal_conv(xbc_in, p["conv_w"], p["conv_b"])
    q, k, v, g, xh, _ = _ssd_core(cfg, p, xbc, dt_raw)
    scan = linear_scan if use_kernel else linear_scan_chunked
    kw = dict(inclusive=True)
    if use_kernel:                      # the kernel takes a decay per channel
        kw["interpret"] = jax.default_backend() != "tpu"
        g = jnp.broadcast_to(g, q.shape)
    y, s_fin = scan(q, k, v, g, None, **kw)                # (B,H,L,P)
    y = y.transpose(0, 2, 1, 3)                            # (B,L,H,P)
    y = y + xh.astype(jnp.float32) * p["d_skip"][None, None, :, None]
    y = y.reshape(x.shape[0], x.shape[1], cfg.d_inner).astype(x.dtype)
    out = dense(p["out_proj"], _gated_norm(cfg, p, y, z))
    if collect:
        # s_fin from the chunked scan is (B,H,Dk,Dv) = (B,H,N,P)
        window = xbc_in[:, -(cfg.ssm_conv - 1):]
        return out, {"conv": window, "ssm": s_fin}
    return out


# ------------------------------------------------------------------ decode

def mamba2_init_cache(cfg: ModelConfig, batch: int, dtype) -> Dict[str, jax.Array]:
    conv_ch = cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_ch), dtype),
        "ssm": jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_state,
                          cfg.ssm_head_dim), jnp.float32),
    }


def mamba2_decode(cfg: ModelConfig, p: Params, x: jax.Array,
                  cache: Dict[str, jax.Array]
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Single-token step. x: (B, 1, d_model)."""
    z, xbc, dt_raw = _ssd_inputs(cfg, p, x)
    conv_in = xbc
    xbc = _causal_conv(xbc, p["conv_w"], p["conv_b"], window=cache["conv"])
    new_conv = jnp.concatenate([cache["conv"], conv_in], axis=1)[:, 1:]
    q, k, v, g, xh, _ = _ssd_core(cfg, p, xbc, dt_raw)
    # one-step recurrence: S' = exp(g) S + k (x) v ; y = q . S'
    s = cache["ssm"]                                       # (B,H,N,P)
    gi = g[:, :, 0].astype(jnp.float32)                    # (B,H,1)
    ki = k[:, :, 0].astype(jnp.float32)
    qi = q[:, :, 0].astype(jnp.float32)
    vi = v[:, :, 0].astype(jnp.float32)                    # (B,H,P)
    s_new = jnp.exp(gi)[..., None] * s + ki[..., None] * vi[:, :, None, :]
    y = jnp.einsum("bhn,bhnp->bhp", qi, s_new)             # (B,H,P)
    y = y + xh[:, 0].astype(jnp.float32) * p["d_skip"][None, :, None]
    y = y.reshape(x.shape[0], 1, cfg.d_inner).astype(x.dtype)
    return (dense(p["out_proj"], _gated_norm(cfg, p, y, z)),
            {"conv": new_conv, "ssm": s_new})
