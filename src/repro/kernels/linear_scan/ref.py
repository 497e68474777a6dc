"""Pure-jnp oracle: gated linear recurrence (shared by RWKV6 and Mamba2).

Semantics (per batch*head, 0-based):
    S_i = diag(exp(g_i)) S_{i-1} + k_i (x) v_i          S_{-1} = S_init
    inclusive:  out_i = q_i^T S_i        (Mamba2 / SSD: y uses updated state)
    exclusive:  out_i = q_i^T S_{i-1}    (RWKV6: state used before decay+update;
                                          the u-bonus term is added by callers)

Shapes: q, k: (B, H, L, Dk); v: (B, H, L, Dv); g (log decay <= 0):
(B, H, L, Dk); S_init: (B, H, Dk, Dv).  Returns (out (B,H,L,Dv), S_final).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def linear_scan_ref(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                    s_init: Optional[jax.Array] = None,
                    inclusive: bool = True) -> Tuple[jax.Array, jax.Array]:
    b, h, l, dk = q.shape
    dv = v.shape[-1]
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    gf = g.astype(jnp.float32)
    s0 = (jnp.zeros((b, h, dk, dv), jnp.float32) if s_init is None
          else s_init.astype(jnp.float32))

    def step(s, inp):
        qi, ki, vi, gi = inp               # (B,H,Dk) / (B,H,Dv) / (B,H,Dk)
        s_new = jnp.exp(gi)[..., None] * s + ki[..., None] * vi[..., None, :]
        used = s_new if inclusive else s
        out = jnp.einsum("bhk,bhkv->bhv", qi, used)
        return s_new, out

    xs = (jnp.moveaxis(qf, 2, 0), jnp.moveaxis(kf, 2, 0),
          jnp.moveaxis(vf, 2, 0), jnp.moveaxis(gf, 2, 0))
    s_fin, outs = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(outs, 0, 2).astype(q.dtype), s_fin


def linear_scan_chunked(q: jax.Array, k: jax.Array, v: jax.Array,
                        g: jax.Array, s_init: Optional[jax.Array] = None,
                        inclusive: bool = True, chunk: int = 64
                        ) -> Tuple[jax.Array, jax.Array]:
    """Chunked-parallel form in pure jnp — the same math as the Pallas
    kernel (all decay exponents <= 0), scanning over CHUNKS instead of
    tokens.  This is what the models lower for training/prefill: the
    per-token scan round-trips the (Dk x Dv) state through HBM every step
    (measured 3.2e5 s memory term on zamba2 train_4k); chunking cuts state
    traffic by the chunk length and turns the work into matmuls.

    ``g`` may hold one decay per head, (B, H, L, 1), as Mamba2's does: the
    intra-chunk scores are then one (C x C) matmul scaled by the decay
    between positions, with no (C x C x Dk) decay tensor."""
    b, h, l, dk = q.shape
    dv = v.shape[-1]
    pad = (-l) % chunk
    if s_init is None:
        s_init = jnp.zeros((b, h, dk, dv), jnp.float32)

    def prep(t, d):
        t = jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0)))
        nc = (l + pad) // chunk
        return t.reshape(b, h, nc, chunk, d).astype(jnp.float32) \
                .transpose(2, 0, 1, 3, 4)          # (NC, B, H, C, D)

    qc, kc, gc = prep(q, dk), prep(k, dk), prep(g, g.shape[-1])
    vc = prep(v, dv)
    ii = jnp.arange(chunk)[:, None]
    jj = jnp.arange(chunk)[None, :]
    mask = (jj <= ii) if inclusive else (jj < ii)

    def body(s, inp):
        q_c, k_c, v_c, g_c = inp                   # (B, H, C, D*)
        c = jnp.cumsum(g_c, axis=-2)
        cq = c if inclusive else c - g_c
        c_last = c[..., -1:, :]                    # (B, H, 1, Dk)
        out = jnp.einsum("bhck,bhkv->bhcv", q_c * jnp.exp(cq), s)
        if g_c.shape[-1] == 1:          # one decay per head
            diff = jnp.where(mask, cq - jnp.swapaxes(c, -1, -2), -jnp.inf)
            scores = jnp.einsum("bhik,bhjk->bhij", q_c, k_c) * jnp.exp(diff)
        else:
            pair = jnp.exp(cq[..., :, None, :] - c[..., None, :, :])
            scores = jnp.einsum("bhik,bhjk,bhijk->bhij", q_c, k_c, pair)
            scores = jnp.where(mask, scores, 0.0)
        out = out + jnp.einsum("bhij,bhjv->bhiv", scores, v_c)
        ke = k_c * jnp.exp(c_last - c)
        s_new = s * jnp.exp(c_last[..., 0, :])[..., None] + \
            jnp.einsum("bhck,bhcv->bhkv", ke, v_c)
        return s_new, out

    s_fin, outs = jax.lax.scan(body, s_init.astype(jnp.float32),
                               (qc, kc, vc, gc))
    out = outs.transpose(1, 2, 0, 3, 4).reshape(b, h, l + pad, dv)
    return out[:, :, :l].astype(q.dtype), s_fin
