"""Random generator weights, made on the device in one jitted call.

The tree is the layout the program's dense decoder takes (stacked
layers, tied embedding, q/k/v biases), in the configuration's dtype.  The
benchmark keeps these arrays: the program gets them to serve with, and
the reference reads them after the program's state is freed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _layout(m: dict):
    d, heads = m["hidden_size"], m["num_attention_heads"]
    hd = d // heads
    kv = m["num_key_value_heads"] * hd
    ff, layers = m["intermediate_size"], m["num_hidden_layers"]
    vocab = -(-m["vocab_size"] // 128) * 128
    # (shape, kind): "e" scales by the width, "w" by the fan-in, "b" is a
    # small bias, "s" a norm scale near one
    return {
        "embed": ((vocab, d), "e"),
        "final_norm": {"scale": ((d,), "s")},
        "layers": {
            "ln1": {"scale": ((layers, d), "s")},
            "ln2": {"scale": ((layers, d), "s")},
            "attn": {"wq": {"w": ((layers, d, heads * hd), "w"),
                            "b": ((layers, heads * hd), "b")},
                     "wk": {"w": ((layers, d, kv), "w"),
                            "b": ((layers, kv), "b")},
                     "wv": {"w": ((layers, d, kv), "w"),
                            "b": ((layers, kv), "b")},
                     "wo": {"w": ((layers, heads * hd, d), "w")}},
            "mlp": {"gate": {"w": ((layers, d, ff), "w")},
                    "up": {"w": ((layers, d, ff), "w")},
                    "down": {"w": ((layers, ff, d), "w")}},
        },
    }


def make(model: dict, seed32: int):
    """Weights for ``model`` (the configuration's ``model`` block) from a
    32-bit seed."""
    dtype = {"bfloat16": jnp.bfloat16,
             "float32": jnp.float32}[model["torch_dtype"]]
    spec = _layout(model)
    leaves, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[1], str))

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, kind) in zip(keys, leaves):
            z = jax.random.normal(k, shape, jnp.float32)
            if kind == "e":
                x = z * shape[-1] ** -0.5
            elif kind == "w":
                x = z * shape[-2] ** -0.5
            elif kind == "b":
                x = 0.1 * z
            else:
                x = 1.0 + 0.1 * z
            out.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return build(jax.random.PRNGKey(seed32))
