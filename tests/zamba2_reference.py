"""Plain float32 reference of the Zamba2 language model, for the tests.

Written from the published block (arXiv:2411.15242; the equations of
transformers' ``modeling_zamba2.py``: ``Zamba2MambaDecoderLayer``,
``Zamba2HybridLayer``, ``Zamba2AttentionDecoderLayer``, ``Zamba2MLP``,
``Zamba2RMSNormGated``), sharing no code with the program.  One sequence
at a time, no cache, no kernels, every matmul at ``highest`` precision:

* ``e`` = the token embedding; ``h`` starts as ``e``.
* Each layer ``i``: ``t`` = 0, or at the ``j``-th hybrid layer the shared
  block's output: block ``j % 2`` on ``x = RMSNorm(concat[h, e])``
  (width ``2 d``); attention with q/k/v from ``x`` (heads of
  ``head_dim``), rotary embedding over each head's two halves, causal
  softmax scaled by ``(head_dim / 2) ** -0.5``, o-projection to ``d``;
  ``RMSNorm`` (``pre_ff_layernorm``); the MLP ``down(gelu(g) * u)`` with
  ``[g, u] = W_gu x + B_j(A_j x)`` (call ``j``'s LoRA) and exact GELU;
  then call ``j``'s ``Linear(d, d)``.  No residual inside the block.
* ``h <- h + mamba(RMSNorm(h + t))``, the Mamba2 mixer: in_proj to
  ``[z | x B C | dt]``; causal depthwise conv (with bias) and SiLU over
  ``x B C``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  B and C in ``groups`` groups, head ``h`` reading group
  ``h // (heads / groups)``; the sequential recurrence
  ``S_t = exp(dt_t A) S_{t-1} + dt_t B_t (x) x_t``,
  ``y_t = C_t . S_t + D x_t``; ``y <- RMSNorm_grouped(y * silu(z))``,
  normalised over each group of ``d_inner / groups`` channels; out_proj.
* A final RMSNorm, and logits against the tied embedding.

Departures: the published torch fallback clamps ``dt`` below at
``time_step_min``; its fused-kernel path, which a deployment runs, does
not, and neither does this.  Weights are read from the program's
parameter tree (the data, not its code): the embedding, ``layers`` (per
layer ``ln`` and ``mamba``), ``shared`` (the two blocks) and ``calls``
(per call ``adapter_in``, ``adapter_out`` and ``linear``).

``variant`` plants the two negative controls of the tests:
``"gate_after_norm"`` (``RMSNorm(y) * silu(z)``) and ``"scale_full"``
(softmax scale ``head_dim ** -0.5``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


def _f(x):
    return jnp.asarray(x, jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, _f(w), precision=_HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f(scale)


def _rope(x, theta):
    t, _, d = x.shape
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
    ang = np.arange(t, dtype=np.float32)[:, None, None] * inv
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _mamba(m, x, cfg, variant):
    t = x.shape[0]
    di, n, g = cfg["d_inner"], cfg["state"], cfg["groups"]
    heads, p = cfg["ssm_heads"], cfg["ssm_head_dim"]
    proj = _mm(x, m["in_proj"]["w"])
    z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * g * n], \
        proj[:, 2 * di + 2 * g * n:]
    w, k = _f(m["conv_w"]), m["conv_w"].shape[0]
    pad = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc], 0)
    conv = sum(pad[i:i + t] * w[i] for i in range(k)) + _f(m["conv_b"])
    conv = jax.nn.silu(conv)
    xs = conv[:, :di].reshape(t, heads, p)
    bm = conv[:, di:di + g * n].reshape(t, g, n)
    cm = conv[:, di + g * n:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + _f(m["dt_bias"]))
    a = -jnp.exp(_f(m["a_log"]))
    grp = np.arange(heads) // (heads // g)

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + dt_t[:, None, None] * b_t[grp][:, :, None] * x_t[:, None, :]
        y = jnp.einsum("hn,hnp->hp", c_t[grp], s, precision=_HI) \
            + _f(m["d_skip"])[:, None] * x_t
        return s, y

    _, y = jax.lax.scan(step, jnp.zeros((heads, n, p)), (xs, bm, cm, dt))
    y = y.reshape(t, di)
    if variant == "gate_after_norm":
        y = _rms(y, m["norm"]["scale"], cfg["eps"]) * jax.nn.silu(z)
    else:
        y = (y * jax.nn.silu(z)).reshape(t, g, di // g)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                              + cfg["eps"])
        y = y.reshape(t, di) * _f(m["norm"]["scale"])
    return _mm(y, m["out_proj"]["w"])


def _shared(blk, call, h, e, cfg, variant):
    t = h.shape[0]
    heads, hd = cfg["heads"], cfg["head_dim"]
    x = _rms(jnp.concatenate([h, e], -1), blk["ln_in"]["scale"], cfg["eps"])
    at = blk["attn"]
    q = _rope(_mm(x, at["wq"]["w"]).reshape(t, heads, hd), cfg["theta"])
    k = _rope(_mm(x, at["wk"]["w"]).reshape(t, heads, hd), cfg["theta"])
    v = _mm(x, at["wv"]["w"]).reshape(t, heads, hd)
    scale = hd ** -0.5 if variant == "scale_full" else (hd / 2) ** -0.5
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) * scale
    s = jnp.where(np.tril(np.ones((t, t), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                   precision=_HI).reshape(t, heads * hd)
    x = _rms(_mm(o, at["wo"]["w"]), blk["ln_ff"]["scale"], cfg["eps"])
    gu = _mm(x, blk["gate_up"]["w"]) + _mm(_mm(x, call["adapter_in"]["w"]),
                                          call["adapter_out"]["w"])
    g, u = jnp.split(gu, 2, -1)
    y = _mm(jax.nn.gelu(g, approximate=False) * u, blk["down"]["w"])
    return _mm(y, call["linear"]["w"])


def logits(weights, tokens, cfg: dict, variant: str = ""):
    """``tokens`` (T,) -> float32 logits (T, vocab).  ``cfg``: ``d_inner``,
    ``state``, ``groups``, ``ssm_heads``, ``ssm_head_dim``, ``heads``,
    ``head_dim``, ``theta``, ``eps``, ``hybrid`` (the hybrid layer ids),
    ``blocks`` and ``vocab``."""
    with jax.default_matmul_precision("highest"):
        emb = _f(weights["embed"])
        e = emb[np.asarray(tokens)]
        h = e
        layers = weights["layers"]
        n = layers["ln"]["scale"].shape[0]
        for i in range(n):
            lw = jax.tree.map(lambda a: a[i], layers)
            t = 0.0
            if i in cfg["hybrid"]:
                j = cfg["hybrid"].index(i)
                blk = jax.tree.map(lambda a: a[j % cfg["blocks"]],
                                   weights["shared"])
                call = jax.tree.map(lambda a: a[j], weights["calls"])
                t = _shared(blk, call, h, e, cfg, variant)
            h = h + _mamba(lw["mamba"], _rms(h + t, lw["ln"]["scale"],
                                            cfg["eps"]), cfg, variant)
        h = _rms(h, weights["final_norm"]["scale"], cfg["eps"])
        return _mm(h, emb.T)[:, :cfg["vocab"]]


def config_of(model_cfg) -> dict:
    """The reference's ``cfg`` from a program ``ModelConfig`` (its sizes
    only)."""
    c = model_cfg
    return {"d_inner": c.ssm_expand * c.d_model, "state": c.ssm_state,
            "groups": c.ssm_groups,
            "ssm_heads": c.ssm_expand * c.d_model // c.ssm_head_dim,
            "ssm_head_dim": c.ssm_head_dim, "heads": c.n_heads,
            "head_dim": c.head_dim, "theta": float(c.rope_theta),
            "eps": c.norm_eps, "hybrid": list(c.hybrid_layers),
            "blocks": c.shared_blocks, "vocab": c.vocab}
