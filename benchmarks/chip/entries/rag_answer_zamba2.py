"""Closed-loop RAG answers through ``RAGPipeline.answer``, generated by
Zamba2 (``configs/hospital600-zamba2-7b.json``).

The loop, the timers and the retrieval checks are ``rag_answer``'s.  This
entry brings the Zamba2 program configuration (checked against the
configuration's published numbers before anything is built, so a
program that cannot run it fails at once), its weights, its FLOP count
(``counts_zamba2``) and the comparison of served tokens with the float32
Zamba2 reference (``reference/zamba2.py``).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from entries import rag_answer
from harness import Check

# published keys the program does not read, and the values it implements
FIXED = {"hidden_act": "gelu", "use_mem_rope": True,
         "use_shared_mlp_adapter": True,
         "use_shared_attention_adapter": False, "add_bias_linear": False,
         "use_conv_bias": True}


def program_config(config: dict):
    """The program's ``ModelConfig`` for the configuration, whose top
    level holds the published config.json's keys, after checking that
    the program's registered architecture is the published one in every
    number but the reduced depth."""
    from repro.configs import get_arch
    m = config
    for k, v in FIXED.items():
        if m[k] != v:
            raise ValueError(f"the program implements {k} = {v!r}, the "
                             f"configuration has {m[k]!r}")
    if m["attention_hidden_size"] != 2 * m["hidden_size"] or \
            m["ffn_hidden_size"] != m["intermediate_size"] or \
            m["n_mamba_heads"] * m["mamba_headdim"] != \
            m["mamba_expand"] * m["hidden_size"] or \
            [i for i, k in enumerate(m["layers_block_type"])
             if k == "hybrid"] != m["hybrid_layer_ids"]:
        raise ValueError("the configuration's derived widths or layer "
                         "list disagree with its own numbers")
    base = get_arch(config["program_arch"])
    kw = dict(
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        head_dim=m["attention_head_dim"], d_ff=m["intermediate_size"],
        vocab=m["vocab_size"], rope_theta=float(m["rope_theta"]),
        tie_embeddings=m["tie_word_embeddings"], ssm_state=m["mamba_d_state"],
        ssm_conv=m["mamba_d_conv"], ssm_head_dim=m["mamba_headdim"],
        ssm_expand=m["mamba_expand"], ssm_groups=m["mamba_ngroups"],
        hybrid_layers=tuple(m["hybrid_layer_ids"]),
        shared_blocks=m["num_mem_blocks"], adapter_rank=m["adapter_rank"],
        norm_eps=float(m["rms_norm_eps"]), dtype=m["torch_dtype"])
    fields = {f.name for f in dataclasses.fields(base)}
    if set(kw) - fields:
        raise ValueError(f"the program's {base.arch_id} has no "
                         f"{sorted(set(kw) - fields)}: it is not the "
                         f"published Zamba2 block")
    cfg = base.replace(**kw)
    if config.get("test_size"):
        return cfg
    published = cfg.replace(n_layers=base.n_layers,
                            hybrid_layers=base.hybrid_layers)
    cut = tuple(i for i in base.hybrid_layers if i < cfg.n_layers)
    if published != base or cfg.hybrid_layers != cut:
        raise ValueError(f"the program's {base.arch_id} differs from the "
                         f"configuration: {base} vs {cfg}")
    return cfg


def _layout(cfg):
    """(shape, kind) of every weight, in the program's tree; the kinds
    are those of :func:`make_weights`."""
    d, di, ff, r = cfg.d_model, cfg.d_inner, cfg.d_ff, cfg.adapter_rank
    gn, heads, k = cfg.ssm_groups * cfg.ssm_state, cfg.ssm_heads, cfg.ssm_conv
    qkv = cfg.n_heads * cfg.head_dim
    n, b, c = cfg.n_layers, cfg.shared_blocks, len(cfg.hybrid_layers)
    return {
        "embed": ((cfg.padded_vocab, d), "e"),
        "final_norm": {"scale": ((d,), "s")},
        "layers": {
            "ln": {"scale": ((n, d), "s")},
            "mamba": {"in_proj": {"w": ((n, d, 2 * di + 2 * gn + heads),
                                        "w")},
                      "conv_w": ((n, k, di + 2 * gn), "conv"),
                      "conv_b": ((n, di + 2 * gn), "b"),
                      "a_log": ((n, heads), "a_log"),
                      "dt_bias": ((n, heads), "dt_bias"),
                      "d_skip": ((n, heads), "skip"),
                      "norm": {"scale": ((n, di), "s")},
                      "out_proj": {"w": ((n, di, d), "w")}}},
        "shared": {
            "ln_in": {"scale": ((b, 2 * d), "s")},
            "attn": {"wq": {"w": ((b, 2 * d, qkv), "w")},
                     "wk": {"w": ((b, 2 * d, qkv), "w")},
                     "wv": {"w": ((b, 2 * d, qkv), "w")},
                     "wo": {"w": ((b, qkv, d), "w")}},
            "ln_ff": {"scale": ((b, d), "s")},
            "gate_up": {"w": ((b, d, 2 * ff), "w")},
            "down": {"w": ((b, ff, d), "w")}},
        "calls": {
            "adapter_in": {"w": ((c, d, r), "w")},
            "adapter_out": {"w": ((c, r, 2 * ff), "w")},
            "linear": {"w": ((c, d, d), "w")}},
    }


# kinds the program keeps in float32 whatever the model's dtype
F32 = ("a_log", "dt_bias", "skip")


def make_weights(cfg, model: dict, seed32: int):
    """Random weights from a 32-bit seed, made on the device in one
    jitted call: normal with a 1/sqrt(fan-in) scale; ``dt`` log-uniform
    in ``[time_step_min, time_step_max]`` through softplus^-1 and
    ``A_log = log U(1, 16)``, as Mamba2 initialises them; norm scales and
    ``D`` near one; conv taps and biases random.  The SSM's ``A_log``,
    ``dt_bias`` and ``D`` are float32, as the program keeps them."""
    import jax
    import jax.numpy as jnp
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]
    lo, hi = np.log(model["time_step_min"]), np.log(model["time_step_max"])
    leaves, treedef = jax.tree.flatten(
        _layout(cfg), is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[1], str))

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for kk, (shape, kind) in zip(keys, leaves):
            if kind == "a_log":
                x = jnp.log(jax.random.uniform(kk, shape, jnp.float32, 1, 16))
            elif kind == "dt_bias":
                dt = jnp.exp(jax.random.uniform(kk, shape, jnp.float32,
                                                lo, hi))
                x = dt + jnp.log(-jnp.expm1(-dt))
            else:
                z = jax.random.normal(kk, shape, jnp.float32)
                if kind == "e":
                    x = z * shape[-1] ** -0.5
                elif kind in ("w", "conv"):           # fan-in, taps
                    x = z * shape[-2] ** -0.5
                elif kind == "b":
                    x = 0.1 * z
                else:                                  # scales and D
                    x = 1.0 + 0.1 * z
            out.append(x if kind in F32 else x.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return build(jax.random.PRNGKey(seed32))


class Entry(rag_answer.Entry):

    def setup(self) -> None:
        # the program's configuration first: a program that is not the
        # published block fails here, before any data or weights
        self.model_cfg = program_config(self.ctx.config)
        # rag_answer reads the model's numbers from a "model" group; this
        # configuration holds them at its top level, as the catalog does
        self.ctx = dataclasses.replace(
            self.ctx, config={**self.ctx.config, "model": self.ctx.config})
        super().setup()

    def build(self):
        import jax
        from repro.data import HashTokenizer
        from repro.models import lm
        from repro.serving import RAGPipeline, ServeEngine

        ctx, cfg = self.ctx, self.model_cfg
        self.weights = make_weights(cfg, ctx.config, ctx.key_seed)
        want = jax.tree.map(lambda a: (a.shape, a.dtype),
                            lm.abstract_params(cfg))
        got = jax.tree.map(lambda a: (a.shape, a.dtype), self.weights)
        if want != got:
            raise ValueError("the weights' layout is not the program's")
        jax.block_until_ready(self.weights)
        s = ctx.config["serving"]
        engine = ServeEngine(cfg, self.weights, cache_size=s["cache_size"],
                             batch_size=s["batch_size"])
        rag = RAGPipeline(self.corpus, engine,
                          tokenizer=HashTokenizer(cfg.vocab), use_bank=True)
        self.t_retrieve, self.t_serve, self.t_maintain = [], [], []
        self.served = []
        serve = engine.serve

        def capture(requests):
            out = serve(requests)
            self.served.extend(requests)
            return out
        timed = rag_answer._timed
        engine.serve = timed(capture, self.t_serve, ctx.annotate, "serve")
        rag.retrieve = timed(rag.retrieve, self.t_retrieve, ctx.annotate,
                             "retrieve")
        rag.maintain = timed(rag.maintain, self.t_maintain, ctx.annotate,
                             "maintain")
        return rag

    def window(self, seconds: float):
        from counts_zamba2 import answer_flops
        win = super().window(seconds)
        m = self.ctx.config
        win.stats["answer_flops"] = float(sum(
            answer_flops(m, len(p), len(o)) for p, o in self.requests))
        return win

    def verify_more(self):
        """As ``rag_answer``'s, against the Zamba2 reference: the widest
        gap by which a served token's float32 reference logit lies below
        the reference's best, over a sample drawn from the seed that
        holds the longest answer and at least ``check_served_tokens``
        tokens.  With the ``fp8_control`` fault the reference computed
        in float8 takes the program's place."""
        import jax.numpy as jnp
        from reference import qwen2, zamba2
        if not self.requests:
            return [Check("logit_gap", float("inf"), self.logit_gap_limit())]
        rng = np.random.default_rng([self.ctx.seed, 3])
        n = len(self.requests)
        longest = max(range(n), key=lambda i: len(self.requests[i][1]))
        order = [longest] + [int(i) for i in rng.permutation(n)
                             if i != longest]
        want, pick, got = self.ctx.traffic["check_served_tokens"], [], 0
        for i in order:
            if got >= want:
                break
            pick.append(i)
            got += len(self.requests[i][1])
        cfg = zamba2.dims(self.ctx.config)
        cache = self.ctx.config["serving"]["cache_size"]
        control = self.ctx.fault == "fp8_control"
        widest = 0.0
        for i in pick:
            prompt, out = self.requests[i]
            seq = prompt + out[:-1]
            toks = np.zeros(cache, np.int32)
            toks[:len(seq)] = seq
            pos = np.arange(len(prompt) - 1, len(seq))
            logits = zamba2.logits(self.weights, jnp.asarray(toks), cfg=cfg)
            served = jnp.asarray(out, np.int32)
            if control:
                low = zamba2.logits(self.weights, jnp.asarray(toks), cfg=cfg,
                                    mode="fp8")
                served = jnp.argmax(low[pos], axis=-1).astype(jnp.int32)
            gap = np.asarray(qwen2.served_gap(logits[pos], served))
            widest = max(widest, float(gap.max()))
        self.win.notes.append(f"generator check: {len(pick)} answers, "
                              f"{got} served tokens")
        return [Check("logit_gap", widest, self.logit_gap_limit())]
