"""Plain float32 reference of a Qwen2 decoder (arXiv:2407.10671).

Written from the published architecture, sharing no code with the
program: token embedding; per layer RMSNorm, grouped-query attention with
biases on q/k/v, rotary position embedding over the two halves of each
head (theta from the config) and a causal softmax scaled by
``head_dim ** -0.5``, a residual, RMSNorm and a SwiGLU MLP, a residual;
a final RMSNorm and logits against the tied embedding.

The weights are the benchmark's own (``entries/weights.py`` makes them
from the seed in the layout the program takes); they are read here in
float32, one layer at a time.  ``mode="fp8"`` is the control: every
linear layer's inputs rounded to float8 e4m3 with a per-tensor scale for
weights and a per-token scale for activations, accumulated in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0


def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _E4M3_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _linear(x, w, mode):
    w = w.astype(jnp.float32)
    if mode == "fp8":
        x, w = _fp8(x, -1), _fp8(w, None)
    return jnp.dot(x, w, precision=_HI)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    # x: (T, H, D); halves rotate together
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


@functools.partial(jax.jit, static_argnames=("cfg", "mode"))
def logits(weights, tokens, *, cfg, mode: str = "f32"):
    """``tokens`` (T,) int32 -> logits (T, vocab) float32.  ``cfg`` is a
    hashable tuple of ``(heads, kv_heads, head_dim, rope_theta,
    rms_eps, vocab)``."""
    heads, kv_heads, hd, theta, eps, vocab = cfg
    t = tokens.shape[0]
    emb = weights["embed"]
    x = emb[tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((t, t), bool))
    group = heads // kv_heads

    def layer(x, lp):
        a = lp["attn"]
        h = _rmsnorm(x, lp["ln1"]["scale"], eps)
        q = _linear(h, a["wq"]["w"], mode) + a["wq"]["b"].astype(jnp.float32)
        k = _linear(h, a["wk"]["w"], mode) + a["wk"]["b"].astype(jnp.float32)
        v = _linear(h, a["wv"]["w"], mode) + a["wv"]["b"].astype(jnp.float32)
        q = _rope(q.reshape(t, heads, hd), theta)
        k = _rope(k.reshape(t, kv_heads, hd), theta)
        v = v.reshape(t, kv_heads, hd)
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) * hd ** -0.5
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=_HI).reshape(t, -1)
        x = x + _linear(o, a["wo"]["w"], mode)
        m = lp["mlp"]
        h = _rmsnorm(x, lp["ln2"]["scale"], eps)
        g = _linear(h, m["gate"]["w"], mode)
        u = _linear(h, m["up"]["w"], mode)
        x = x + _linear(jax.nn.silu(g) * u, m["down"]["w"], mode)
        return x, None

    x, _ = jax.lax.scan(layer, x, weights["layers"])
    x = _rmsnorm(x, weights["final_norm"]["scale"], eps)
    out = _linear(x, emb.T, mode)
    return out[:, :vocab]


@jax.jit
def served_gap(ref_logits, served):
    """Per position: how far the served token's reference logit lies
    below the reference's best."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, served[:, None], axis=-1)[:, 0]
    return best - got
