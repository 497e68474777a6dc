"""Mixture-of-Experts layer: top-k router + sort-based capacity dispatch.

Dispatch is the sort/ragged formulation (not the (T,E,C) one-hot einsum,
which is O(T^2 k) memory at pod batch sizes): assignments are sorted by
expert, each expert's first C tokens are scattered into an (E, C, D) buffer
(token-order priority, overflow dropped — standard capacity dropping), the
expert SwiGLU runs as one batched einsum over E, and results gather back
weighted by router probabilities.  Experts shard over the "model" mesh axis
(EP); the sort/scatter lowers to all_to_all under GSPMD.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import Params, dense_init, swiglu


def init_moe(key, cfg: ModelConfig, dtype) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    scale = d ** -0.5
    p = {
        "router": (jax.random.normal(ks[0], (d, e), jnp.float32)
                   * scale).astype(jnp.float32),       # router kept f32
        "w_gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32)
                   * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, f), jnp.float32)
                 * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d), jnp.float32)
                   * f ** -0.5).astype(dtype),
    }
    if cfg.shared_expert:
        sks = jax.random.split(ks[4], 3)
        p["shared"] = {
            "gate": dense_init(sks[0], d, f, dtype),
            "up": dense_init(sks[1], d, f, dtype),
            "down": dense_init(sks[2], f, d, dtype),
        }
    return p


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    c = math.ceil(tokens * cfg.top_k / cfg.num_experts * cfg.capacity_factor)
    return max(4, -(-c // 4) * 4)      # round up to a multiple of 4


def moe_apply(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    """x: (B, L, D) -> (B, L, D).  Routed (+ shared) expert output.

    With an ambient mesh (runtime.set_mesh) this takes the explicit
    shard_map expert-parallel path; otherwise the single-device path."""
    from . import runtime
    if runtime.get_mesh() is not None:
        return moe_apply_sharded(cfg, p, x, runtime.get_mesh(),
                                 runtime.dp_axes(), runtime.tp_axis())
    return _moe_apply_local(cfg, p, x)


def _moe_apply_local(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    b, l, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    t = b * l
    xt = x.reshape(t, d)

    logits = xt.astype(jnp.float32) @ p["router"]            # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                   # (T, k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)

    # ---- cumsum-ranked dispatch (sort-free) -----------------------------
    # position_in_expert via exclusive cumsum of assignment one-hots.
    # A global argsort here costs thousands of collective-permutes under
    # GSPMD; the cumsum ranks with one small prefix-scan instead.
    flat_e = top_e.reshape(t * k)                            # (Tk,) token-major
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)      # (Tk, E)
    pos_all = jnp.cumsum(onehot, axis=0) - onehot            # exclusive
    pos = jnp.take_along_axis(pos_all, flat_e[:, None], axis=1)[:, 0]

    cap = _capacity(t, cfg)
    keep = pos < cap
    slot = jnp.where(keep, pos, cap)
    buf = jnp.zeros((e, cap, d), x.dtype)
    buf = buf.at[flat_e, slot].set(xt[flat_tok], mode="drop")  # (E, C, D)

    # ---- expert computation (one batched einsum per matrix) ------------
    h_gate = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    h_up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = swiglu(h_gate, h_up)
    y_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"])       # (E, C, D)

    # ---- combine --------------------------------------------------------
    y_flat = y_buf.at[flat_e, slot].get(mode="fill",
                                        fill_value=0)        # (Tk, D)
    y_flat = jnp.where(keep[:, None], y_flat, 0).reshape(t, k, d)
    out = jnp.einsum("tkd,tk->td", y_flat.astype(jnp.float32),
                     top_p).astype(x.dtype)

    if "shared" in p:
        s = p["shared"]
        shared = swiglu(xt @ s["gate"]["w"], xt @ s["up"]["w"]) @ s["down"]["w"]
        out = out + shared
    return out.reshape(b, l, d)


def moe_apply_sharded(cfg: ModelConfig, p: Params, x: jax.Array, mesh,
                      dp_axes, tp_axis: str) -> jax.Array:
    """Expert-parallel MoE as an explicit shard_map region.

    Plain GSPMD lowering of token dispatch (global gathers/cumsum over all
    tokens) replicates activations across the mesh and drags the whole
    layer's layouts with it (observed: 10x flops + 500 GiB collectives per
    step on the 256-chip dry-run).  Here instead:

      * routing + capacity ranking are LOCAL to each data shard (zero comm);
      * expert weights stay (E over tp) x (D over dp=FSDP); the dp shards
        all_gather their weight slice (the FSDP gather GSPMD would emit
        anyway) and each tp shard computes only its own E/tp experts;
      * each tp shard combines its experts' outputs for local tokens; one
        psum over tp completes the token outputs (bytes: T_local x D —
        thousands of times smaller than the auto-partitioned lowering);
      * the shared expert (llama4) runs megatron-style on the same psum.
    """
    import functools
    from jax.sharding import PartitionSpec as P

    e, k, d, f = cfg.num_experts, cfg.top_k, cfg.d_model, cfg.d_ff
    tp = mesh.shape[tp_axis]
    e_per = e // tp
    has_shared = "shared" in p

    in_specs = [P(dp_axes, None, None),                 # x
                P(),                                    # router (replicated)
                P(tp_axis, dp_axes, None),              # w_gate (E, D, F)
                P(tp_axis, dp_axes, None),              # w_up
                P(tp_axis, None, dp_axes)]              # w_down (E, F, D)
    args = [x, p["router"], p["w_gate"], p["w_up"], p["w_down"]]
    if has_shared:
        in_specs += [P(dp_axes, tp_axis), P(dp_axes, tp_axis),
                     P(tp_axis, dp_axes)]
        args += [p["shared"]["gate"]["w"], p["shared"]["up"]["w"],
                 p["shared"]["down"]["w"]]

    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    t_global = x.shape[0] * x.shape[1]
    # decode / tiny-batch: moving the FSDP-gathered expert weights costs
    # GB/step while all tokens fit in MB — route tokens instead (replicate
    # tokens, partial contractions against the *resident* weight shards,
    # psum).  Measured on llama4 decode_32k: 99 GiB -> ~0.2 GiB per step.
    if t_global * max(k, 1) <= 4096:
        return _moe_small_batch(cfg, p, x, mesh, dp_axes, tp_axis, dp_size)

    def inner(x_l, router, wg, wu, wd, *shared_w):
        b_l, l_l, _ = x_l.shape
        t = b_l * l_l
        xt = x_l.reshape(t, d)
        logits = xt.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(
            jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)

        # local cumsum ranking + capacity (per data shard)
        flat_e = top_e.reshape(t * k)
        flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
        pos_all = jnp.cumsum(onehot, axis=0) - onehot
        pos = jnp.take_along_axis(pos_all, flat_e[:, None], axis=1)[:, 0]
        cap = _capacity(t, cfg)
        keep = pos < cap
        slot = jnp.where(keep, pos, cap)

        # build ONLY the owned expert slice: a replicated full buffer would
        # need its (E, C, D) cotangent all-reduced over tp in the backward
        # pass (observed 60 GiB/step); the owned slice keeps bwd local and
        # the d_xt psum is just (T_local, D).
        my0 = jax.lax.axis_index(tp_axis) * e_per
        owned = (flat_e >= my0) & (flat_e < my0 + e_per) & keep
        rel = jnp.clip(flat_e - my0, 0, e_per - 1)
        my_buf = jnp.zeros((e_per, cap, d), x.dtype)
        my_buf = my_buf.at[jnp.where(owned, rel, e_per), slot].set(
            xt[flat_tok], mode="drop")

        # FSDP weight gather (dp axis)
        wg_full = jax.lax.all_gather(wg, dp_axes, axis=1, tiled=True)
        wu_full = jax.lax.all_gather(wu, dp_axes, axis=1, tiled=True)
        wd_full = jax.lax.all_gather(wd, dp_axes, axis=2, tiled=True)

        h = swiglu(jnp.einsum("ecd,edf->ecf", my_buf, wg_full),
                   jnp.einsum("ecd,edf->ecf", my_buf, wu_full))
        y_my = jnp.einsum("ecf,efd->ecd", h, wd_full)   # (E/tp, C, D)

        # local combine of owned experts' outputs
        vals = y_my.at[rel, slot].get(mode="fill", fill_value=0)
        vals = jnp.where(owned[:, None], vals, 0).reshape(t, k, d)
        y = jnp.einsum("tkd,tk->td", vals.astype(jnp.float32), top_p)

        if shared_w:
            sg, su, sd = shared_w
            sg = jax.lax.all_gather(sg, dp_axes, axis=0, tiled=True)
            su = jax.lax.all_gather(su, dp_axes, axis=0, tiled=True)
            sd = jax.lax.all_gather(sd, dp_axes, axis=1, tiled=True)
            hs = swiglu(xt @ sg, xt @ su)                # F/tp local
            y = y + (hs @ sd).astype(jnp.float32)        # partial over tp

        y = jax.lax.psum(y.astype(jnp.float32), tp_axis)
        return y.astype(x.dtype).reshape(b_l, l_l, d)

    fn = jax.shard_map(inner, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=P(dp_axes, None, None))
    return fn(*args)


def _moe_small_batch(cfg: ModelConfig, p: Params, x: jax.Array, mesh,
                     dp_axes, tp_axis: str, dp_size: int) -> jax.Array:
    """Token-routed MoE for decode-scale batches: weights never move.

    Tokens are all_gathered over dp (MBs); every (dp, tp) cell computes the
    partial expert contraction against its RESIDENT weight shard
    (E/tp experts x D/dp rows); psum over dp completes the contraction,
    psum over tp combines expert outputs; a final dp all_gather reassembles
    the D dimension."""
    import functools
    from jax.sharding import PartitionSpec as P

    e, k, d, f = cfg.num_experts, cfg.top_k, cfg.d_model, cfg.d_ff
    tp = mesh.shape[tp_axis]
    e_per = e // tp
    d_per = d // dp_size
    has_shared = "shared" in p

    in_specs = [P(dp_axes, None, None), P(),
                P(tp_axis, dp_axes, None), P(tp_axis, dp_axes, None),
                P(tp_axis, None, dp_axes)]
    args = [x, p["router"], p["w_gate"], p["w_up"], p["w_down"]]
    if has_shared:
        in_specs += [P(dp_axes, tp_axis), P(dp_axes, tp_axis),
                     P(tp_axis, dp_axes)]
        args += [p["shared"]["gate"]["w"], p["shared"]["up"]["w"],
                 p["shared"]["down"]["w"]]

    def inner(x_l, router, wg, wu, wd, *shared_w):
        b_l, l_l, _ = x_l.shape
        t_loc = b_l * l_l
        xt = jax.lax.all_gather(x_l.reshape(t_loc, d), dp_axes, axis=0,
                                tiled=True)               # (T, D) replicated
        t = xt.shape[0]
        logits = xt.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(
            jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)

        flat_e = top_e.reshape(t * k)
        flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - onehot,
                                  flat_e[:, None], axis=1)[:, 0]
        cap = _capacity(t, cfg)
        keep = pos < cap
        slot = jnp.where(keep, pos, cap)
        my0 = jax.lax.axis_index(tp_axis) * e_per
        owned = (flat_e >= my0) & (flat_e < my0 + e_per) & keep
        rel = jnp.clip(flat_e - my0, 0, e_per - 1)
        my_buf = jnp.zeros((e_per, cap, d), x.dtype)
        my_buf = my_buf.at[jnp.where(owned, rel, e_per), slot].set(
            xt[flat_tok].astype(x.dtype), mode="drop")

        # partial contraction over the local D/dp slice — weights resident
        dp_idx = jax.lax.axis_index(dp_axes)              # linear over dp
        d_lo = dp_idx * d_per
        buf_slice = jax.lax.dynamic_slice_in_dim(my_buf, d_lo, d_per, axis=2)
        gate = jax.lax.psum(
            jnp.einsum("ecd,edf->ecf", buf_slice.astype(jnp.float32),
                       wg.astype(jnp.float32)), dp_axes)
        up = jax.lax.psum(
            jnp.einsum("ecd,edf->ecf", buf_slice.astype(jnp.float32),
                       wu.astype(jnp.float32)), dp_axes)
        h = swiglu(gate, up)
        y_p = jnp.einsum("ecf,efd->ecd", h, wd.astype(jnp.float32))
        # (E/tp, cap, D/dp): output D-slice per dp shard

        vals = y_p.at[rel, slot].get(mode="fill", fill_value=0)
        vals = jnp.where(owned[:, None], vals, 0).reshape(t, k, d_per)
        y = jnp.einsum("tkd,tk->td", vals, top_p)          # (T, D/dp)

        if shared_w:
            sg, su, sd = shared_w                          # (D/dp, F/tp)...
            x_slice = jax.lax.dynamic_slice_in_dim(xt, d_lo, d_per, axis=1)
            hs_g = jax.lax.psum(x_slice.astype(jnp.float32)
                                @ sg.astype(jnp.float32), dp_axes)
            hs_u = jax.lax.psum(x_slice.astype(jnp.float32)
                                @ su.astype(jnp.float32), dp_axes)
            hs = swiglu(hs_g, hs_u)                        # (T, F/tp)
            y = y + hs @ sd.astype(jnp.float32)            # (T, D/dp) partial
        y = jax.lax.psum(y, tp_axis)                       # (T, D/dp) exact
        y_full = jax.lax.all_gather(y, dp_axes, axis=1, tiled=True)  # (T, D)
        mine = jax.lax.dynamic_slice_in_dim(
            y_full, dp_idx * t_loc, t_loc, axis=0)
        return mine.astype(x.dtype).reshape(b_l, l_l, d)

    fn = jax.shard_map(inner, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=P(dp_axes, None, None))
    return fn(*args)


def aux_load_balance_loss(cfg: ModelConfig, x: jax.Array, p: Params
                          ) -> jax.Array:
    """Switch-style load-balance auxiliary (mean prob x mean assignment)."""
    t = x.shape[0] * x.shape[1]
    logits = x.reshape(t, -1).astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_e = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top_e, cfg.num_experts), axis=0)
    return cfg.num_experts * jnp.sum(frac * jnp.mean(probs, axis=0))
