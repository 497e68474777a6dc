"""The plain references agree with the program where both are exact: the
retrieval reference's hashes, fingerprints and node numbering, and the
float32 Qwen2 reference's logits at a small size on the CPU."""
from __future__ import annotations

import numpy as np
import pytest

from reference import qwen2
from reference.forest import Forest, compare, fingerprints, fnv1a32_many


@pytest.fixture(scope="module")
def corpus():
    from repro.data import hospital_corpus
    return hospital_corpus(num_trees=12)


def test_hashes_and_fingerprints(corpus):
    from repro.core import hashing
    ref = Forest.from_edges(corpus.trees)
    assert np.array_equal(fnv1a32_many(ref.names),
                          hashing.hash_entities(ref.names).astype(np.uint64))
    h = ref.entity_hash.astype(np.uint32)
    assert np.array_equal(fingerprints(h), hashing.fingerprint(h)
                          .astype(np.int64))


def test_forest_numbering(corpus):
    from repro.core import build_forest
    ref = Forest.from_edges(corpus.trees)
    prog = build_forest(corpus.trees)
    assert ref.num_nodes == prog.num_nodes
    assert np.array_equal(ref.parent, np.asarray(prog.parent))
    assert np.array_equal(ref.entity, np.asarray(prog.entity_id))
    assert ref.names == list(prog.entity_names)


def test_compare_catches_a_wrong_walk(corpus):
    ref = Forest.from_edges(corpus.trees)
    nodes = np.arange(0, ref.num_nodes, 7)
    trees, hashes = ref.tree[nodes], ref.entity_hash[ref.entity[nodes]]
    locs = np.full((nodes.size, 4), -1)
    locs[:, 0] = nodes
    up = np.full((nodes.size, 4, 3), -1)
    down = np.full((nodes.size, 4, 3), -1)
    for i, x in enumerate(nodes):
        up[i, 0], down[i, 0] = ref.up(int(x), 3), ref.down(int(x), 3)
    hit = np.ones(nodes.size, bool)
    assert compare(ref, trees, hashes, hit, locs, up, down).wrong == 0
    up[3, 0, 0] = -1 if up[3, 0, 0] >= 0 else 0
    assert compare(ref, trees, hashes, hit, locs, up, down).wrong == 1
    hit[5] = False
    assert compare(ref, trees, hashes, hit, locs, up, down).wrong == 2


def test_qwen2_reference_matches_the_program_in_float32():
    import jax.numpy as jnp
    from entries import weights
    from repro.configs import get_arch
    from repro.models import lm
    m = {"hidden_size": 64, "intermediate_size": 96,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 3, "vocab_size": 300, "rope_theta": 10000.0,
         "rms_norm_eps": 1e-6, "torch_dtype": "float32"}
    cfg = get_arch("qwen2-0.5b").replace(
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=96, vocab=300, rope_theta=10000.0, dtype="float32",
        attn_impl="reference")
    w = weights.make(m, 123)
    toks = np.random.default_rng(0).integers(0, 300, 40).astype(np.int32)
    prog = np.asarray(lm.forward(cfg, w, {"tokens": jnp.asarray(toks)[None]})
                      )[0, :, :300]
    ref = np.asarray(qwen2.logits(w, jnp.asarray(toks),
                                  cfg=(4, 2, 16, 10000.0, 1e-6, 300)))
    np.testing.assert_allclose(ref, prog, atol=2e-4, rtol=0)
    gap = np.asarray(qwen2.served_gap(jnp.asarray(ref),
                                      jnp.asarray(ref.argmax(-1))))
    assert np.all(gap == 0)
    low = np.asarray(qwen2.logits(w, jnp.asarray(toks), mode="fp8",
                                  cfg=(4, 2, 16, 10000.0, 1e-6, 300)))
    assert np.abs(low - ref).max() > 1e-3
