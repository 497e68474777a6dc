"""Work counts: what an algorithm needs, computed from shapes.

Nothing here reads the arena size or how a kernel is written, so a later
kernel that does less work reads higher on the same yardstick.  Bytes
are those of the stored words (4-byte fingerprints, ids and counters).
"""
from __future__ import annotations

WORD = 4          # bytes of every stored fingerprint, id, offset, counter


def probe_kernel_bytes(slots: int, hit: bool) -> int:
    """Bytes the cuckoo probe of one ``(tree, hash)`` query moves: the
    query's hash, segment start and bucket mask read; the fingerprints of
    both candidate buckets, ``2 * slots`` words, read; the matched slot's
    payload read on a hit; hit, payload, bucket and slot written."""
    return WORD * (3 + 2 * slots + (1 if hit else 0) + 4)


def retrieval_bytes(slots: int, max_locs: int, n: int, hit: bool) -> int:
    """Bytes one ``(tree, hash)`` retrieval moves end to end:

    * tree id and hash read, the tree's segment start and bucket count
      read (4 words);
    * the probe: ``2 * slots`` fingerprints read, the matched payload
      read on a hit;
    * on a hit, the temperature bump (one counter read and written), the
      CSR location window (two offsets and the entity's one node in the
      tree: an entity has at most one node per tree), the ``n``-step
      upward walk (parent and entity id per step) and the ``n``-step
      breadth-first downward walk (two child offsets, one child index
      and one entity id per step);
    * the outputs written: hit, ``max_locs`` locations and ``max_locs x
      n`` ids each for up and down.
    """
    words = 4 + 2 * slots
    if hit:
        words += 1 + 2 + 3 + 2 * n + 4 * n
    out = 1 + max_locs + 2 * max_locs * n
    return WORD * (words + out)


def decoder_layer_matmul_params(d: int, heads: int, kv_heads: int,
                                head_dim: int, ff: int) -> int:
    """Weights a token multiplies through in one dense GQA layer with a
    SwiGLU MLP: q, k, v and o projections and gate, up and down."""
    return (d * heads * head_dim + 2 * d * kv_heads * head_dim
            + heads * head_dim * d + 3 * d * ff)


def decoder_token_flops(model: dict, context: int, head: bool) -> int:
    """Forward FLOPs of one token at position ``context - 1``: two per
    multiply-add of every layer's projections and MLP, the attention
    scores and weighted values over ``context`` keys (``4 * heads *
    head_dim * context`` per layer), and the vocabulary projection when
    the token's logits are needed.  Norms, rotary embedding and softmax
    are left out (under 0.1% at these widths)."""
    d, heads = model["hidden_size"], model["num_attention_heads"]
    hd = d // heads
    layers = model["num_hidden_layers"]
    per_layer = 2 * decoder_layer_matmul_params(
        d, heads, model["num_key_value_heads"], hd,
        model["intermediate_size"]) + 4 * heads * hd * context
    return layers * per_layer + (2 * d * model["vocab_size"] if head else 0)


def answer_flops(model: dict, prompt: int, new_tokens: int) -> int:
    """Forward FLOPs of one greedy answer: every prompt token through the
    layers with logits for the last one only, then ``new_tokens - 1``
    decode steps, each through the layers and the vocabulary projection
    (the first served token comes from the prompt's logits).  Padding
    rows and positions are not counted."""
    total = sum(decoder_token_flops(model, p + 1, head=(p == prompt - 1))
                for p in range(prompt))
    total += sum(decoder_token_flops(model, prompt + j + 1, head=True)
                 for j in range(new_tokens - 1))
    return total
