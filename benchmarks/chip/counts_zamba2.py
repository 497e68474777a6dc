"""Work counts of the Zamba2 generator, from the configuration's shapes.

Forward FLOPs, two per multiply-add, of the work the published block
needs for a token at position ``context - 1``:

* every Mamba2 layer: in_proj, the depthwise conv, out_proj, and the
  recurrence ``4 * heads * state * head_dim`` (the state's update and
  its read-out, as the sequential form does it; the chunked scan the
  program runs does more);
* every shared-block call: q, k, v and o, the MLP's gate/up with the
  call's LoRA and down, the call's linear, and the attention scores and
  weighted values over ``context`` keys (``4 * heads * head_dim *
  context``);
* the vocabulary projection when the token's logits are needed.

Norms, rotary embedding, softmax and gates are left out (under 0.1% at
these widths).  Nothing here reads how the program computes.
"""
from __future__ import annotations


def _widths(model: dict):
    d = model["hidden_size"]
    di = model["mamba_expand"] * d
    gn = model["mamba_ngroups"] * model["mamba_d_state"]
    return d, di, gn


def mamba_layer_flops(model: dict) -> int:
    d, di, gn = _widths(model)
    heads = model["n_mamba_heads"]
    conv = di + 2 * gn
    return (2 * d * (2 * di + 2 * gn + heads) + 2 * model["mamba_d_conv"]
            * conv + 2 * di * d
            + 4 * heads * model["mamba_d_state"] * model["mamba_headdim"])


def shared_call_flops(model: dict, context: int) -> int:
    d = model["hidden_size"]
    a = model["attention_hidden_size"]
    qkv = model["num_attention_heads"] * model["attention_head_dim"]
    ff, r = model["intermediate_size"], model["adapter_rank"]
    return (2 * 3 * a * qkv + 2 * qkv * d
            + 2 * d * 2 * ff + 2 * (d * r + r * 2 * ff) + 2 * ff * d
            + 2 * d * d + 4 * qkv * context)


def token_flops(model: dict, context: int, head: bool) -> int:
    calls = len(model["hybrid_layer_ids"])
    return (model["num_hidden_layers"] * mamba_layer_flops(model)
            + calls * shared_call_flops(model, context)
            + (2 * model["hidden_size"] * model["vocab_size"] if head else 0))


def answer_flops(model: dict, prompt: int, new_tokens: int) -> int:
    """Forward FLOPs of one greedy answer: every prompt token through the
    layers with logits for the last one only, then ``new_tokens - 1``
    decode steps, each through the layers and the vocabulary projection.
    Padding rows and positions are not counted."""
    total = sum(token_flops(model, p + 1, head=(p == prompt - 1))
                for p in range(prompt))
    total += sum(token_flops(model, prompt + j + 1, head=True)
                 for j in range(new_tokens - 1))
    return total
