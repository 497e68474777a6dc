"""Plain float32 reference of the Zamba2 language model (arXiv:2411.15242).

Written from the published block (the equations of transformers'
``modeling_zamba2.py``: ``Zamba2MambaDecoderLayer``,
``Zamba2HybridLayer``, ``Zamba2AttentionDecoderLayer``, ``Zamba2MLP``,
``Zamba2RMSNormGated``), sharing no code with the program:

* ``e`` = the token embedding; ``h`` starts as ``e``.
* Each layer ``i``: ``t`` = 0, or at the ``j``-th hybrid layer the shared
  block's output: block ``j % num_mem_blocks`` on
  ``x = RMSNorm(concat[h, e])`` (width ``attention_hidden_size``);
  attention with q/k/v from ``x`` (heads of ``attention_head_dim``),
  rotary embedding over each head's two halves, causal softmax scaled by
  ``(attention_head_dim / 2) ** -0.5``, o-projection to ``hidden_size``;
  RMSNorm (``pre_ff_layernorm``); the MLP ``down(gelu(g) * u)`` with
  ``[g, u] = W_gu x + B_j(A_j x)`` (call ``j``'s LoRA) and exact GELU;
  then call ``j``'s ``Linear(hidden, hidden)``.  No residual inside.
* ``h <- h + mamba(RMSNorm(h + t))``: in_proj to ``[z | x B C | dt]``;
  causal depthwise conv with bias and SiLU over ``x B C``;
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; B and C in
  ``mamba_ngroups`` groups, head ``h`` reading group
  ``h // (heads / groups)``; the sequential recurrence
  ``S_t = exp(dt_t A) S_{t-1} + dt_t B_t (x) x_t``,
  ``y_t = C_t . S_t + D x_t`` (not chunked);
  ``y <- RMSNorm_grouped(y * silu(z))`` over each group of
  ``d_inner / groups`` channels; out_proj.
* A final RMSNorm, and logits against the tied embedding.

Departures: the published torch fallback clamps ``dt`` below at
``time_step_min``; its fused-kernel path, which a deployment runs, does
not, and neither does this.  The weights are the benchmark's own
(``entries/rag_answer_zamba2.py`` makes them from the seed in the layout
the program takes); they are read here in float32, one layer at a time:
each layer is one jitted call, so that a sequence of the cache's length
fits beside the weights.  ``mode="fp8"`` is the control: every linear
layer's inputs rounded to float8 e4m3 with a per-tensor scale for
weights and a per-token scale for activations, accumulated in float32.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0


class Dims(NamedTuple):
    """The sizes the reference needs, from the published config.json's
    keys (:func:`dims`)."""
    d_inner: int
    state: int
    groups: int
    ssm_heads: int
    ssm_head_dim: int
    heads: int
    head_dim: int
    theta: float
    eps: float
    hybrid: Tuple[int, ...]
    blocks: int
    vocab: int


def dims(model: dict) -> Dims:
    return Dims(d_inner=model["mamba_expand"] * model["hidden_size"],
                state=model["mamba_d_state"], groups=model["mamba_ngroups"],
                ssm_heads=model["n_mamba_heads"],
                ssm_head_dim=model["mamba_headdim"],
                heads=model["num_attention_heads"],
                head_dim=model["attention_head_dim"],
                theta=float(model["rope_theta"]),
                eps=float(model["rms_norm_eps"]),
                hybrid=tuple(model["hybrid_layer_ids"]),
                blocks=model["num_mem_blocks"], vocab=model["vocab_size"])


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


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, theta):
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


@functools.partial(jax.jit, static_argnames=("cfg", "mode"))
def _mamba_layer(lw, h, t, *, cfg: Dims, mode: str):
    """``h + mamba(RMSNorm(h + t))`` for one layer."""
    with jax.default_matmul_precision("highest"):
        n_tok = h.shape[0]
        di, n, g = cfg.d_inner, cfg.state, cfg.groups
        heads, p = cfg.ssm_heads, cfg.ssm_head_dim
        m = lw["mamba"]
        x = _rms(h + t, lw["ln"]["scale"], cfg.eps)
        proj = _linear(x, m["in_proj"]["w"], mode)
        z, xbc, dt = (proj[:, :di], proj[:, di:2 * di + 2 * g * n],
                      proj[:, 2 * di + 2 * g * n:])
        w = m["conv_w"].astype(jnp.float32)
        k = w.shape[0]
        pad = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc], 0)
        conv = sum(pad[i:i + n_tok] * w[i] for i in range(k)) \
            + m["conv_b"].astype(jnp.float32)
        conv = jax.nn.silu(conv)
        xs = conv[:, :di].reshape(n_tok, heads, p)
        bm = conv[:, di:di + g * n].reshape(n_tok, g, n)
        cm = conv[:, di + g * n:].reshape(n_tok, g, n)
        dt = jax.nn.softplus(dt + m["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(m["a_log"].astype(jnp.float32))
        d_skip = m["d_skip"].astype(jnp.float32)
        grp = np.arange(heads) // (heads // g)

        def step(s, inp):
            x_t, b_t, c_t, dt_t = inp
            s = jnp.exp(dt_t * a)[:, None, None] * s \
                + dt_t[:, None, None] * b_t[grp][:, :, None] \
                * x_t[:, None, :]
            y = jnp.einsum("hn,hnp->hp", c_t[grp], s, precision=_HI) \
                + d_skip[:, None] * x_t
            return s, y

        _, y = jax.lax.scan(step, jnp.zeros((heads, n, p), jnp.float32),
                            (xs, bm, cm, dt))
        y = (y.reshape(n_tok, di) * jax.nn.silu(z)).reshape(n_tok, g, -1)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.eps)
        y = y.reshape(n_tok, di) * m["norm"]["scale"].astype(jnp.float32)
        return h + _linear(y, m["out_proj"]["w"], mode)


@functools.partial(jax.jit, static_argnames=("cfg", "mode"))
def _shared_call(blk, call, h, e, *, cfg: Dims, mode: str):
    """The shared block's output ``t`` for one call."""
    with jax.default_matmul_precision("highest"):
        n_tok = h.shape[0]
        heads, hd = cfg.heads, cfg.head_dim
        x = _rms(jnp.concatenate([h, e], -1), blk["ln_in"]["scale"], cfg.eps)
        at = blk["attn"]
        q = _rope(_linear(x, at["wq"]["w"], mode).reshape(n_tok, heads, hd),
                  cfg.theta)
        k = _rope(_linear(x, at["wk"]["w"], mode).reshape(n_tok, heads, hd),
                  cfg.theta)
        v = _linear(x, at["wv"]["w"], mode).reshape(n_tok, heads, hd)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) \
            * (hd / 2) ** -0.5
        causal = jnp.tril(jnp.ones((n_tok, n_tok), bool))
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                       precision=_HI).reshape(n_tok, heads * hd)
        x = _rms(_linear(o, at["wo"]["w"], mode), blk["ln_ff"]["scale"],
                 cfg.eps)
        gu = _linear(x, blk["gate_up"]["w"], mode) + _linear(
            _linear(x, call["adapter_in"]["w"], mode),
            call["adapter_out"]["w"], mode)
        g, u = jnp.split(gu, 2, -1)
        y = _linear(jax.nn.gelu(g, approximate=False) * u, blk["down"]["w"],
                    mode)
        return _linear(y, call["linear"]["w"], mode)


@functools.partial(jax.jit, static_argnames=("cfg", "mode"))
def _head(emb, final, h, *, cfg: Dims, mode: str):
    with jax.default_matmul_precision("highest"):
        h = _rms(h, final, cfg.eps)
        return _linear(h, emb.T, mode)[:, :cfg.vocab]


def logits(weights, tokens, *, cfg: Dims, mode: str = "f32"):
    """``tokens`` (T,) int32 -> logits (T, vocab) float32, a layer at a
    time."""
    emb = weights["embed"]
    e = emb[tokens].astype(jnp.float32)
    h = e
    layers = weights["layers"]
    for i in range(layers["ln"]["scale"].shape[0]):
        t = jnp.zeros_like(h)
        if i in cfg.hybrid:
            j = cfg.hybrid.index(i)
            blk = jax.tree.map(lambda a: a[j % cfg.blocks], weights["shared"])
            call = jax.tree.map(lambda a: a[j], weights["calls"])
            t = _shared_call(blk, call, h, e, cfg=cfg, mode=mode)
        h = _mamba_layer(jax.tree.map(lambda a: a[i], layers), h, t,
                         cfg=cfg, mode=mode)
    return _head(emb, weights["final_norm"]["scale"], h, cfg=cfg, mode=mode)
