"""Serving engine + end-to-end CFT-RAG pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.data import HashTokenizer, hospital_corpus
from repro.models import init_params
from repro.serving import RAGPipeline, Request, ServeEngine, kv_cache_bytes


def _engine(cache=128, batch=2):
    cfg = get_arch("qwen2-0.5b").smoke()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, ServeEngine(cfg, params, cache_size=cache, batch_size=batch)


def test_generate_shapes_and_determinism():
    cfg, eng = _engine()
    toks = jnp.asarray(np.random.default_rng(0).integers(4, cfg.vocab,
                                                         (2, 16)), jnp.int32)
    out1 = eng.generate({"tokens": toks}, max_new_tokens=5)
    out2 = eng.generate({"tokens": toks}, max_new_tokens=5)
    assert out1.shape == (2, 5)
    np.testing.assert_array_equal(out1, out2)      # greedy => deterministic


def test_scheduler_truncation_and_batching():
    cfg, eng = _engine(cache=64, batch=2)
    reqs = [Request(prompt_ids=list(range(4, 200)), max_new_tokens=4),
            Request(prompt_ids=list(range(4, 20)), max_new_tokens=4),
            Request(prompt_ids=list(range(4, 40)), max_new_tokens=4)]
    done = eng.serve(reqs)
    assert len(done) == 3
    assert all(len(r.out_ids) == 4 for r in done)
    assert len(done[0].prompt_ids) <= 60           # truncated to window


def test_rag_end_to_end_and_accuracy_proxy():
    corpus = hospital_corpus(num_trees=12, num_queries=6)
    cfg, eng = _engine(cache=128)
    rag = RAGPipeline(corpus, eng, tokenizer=HashTokenizer(cfg.vocab),
                      num_buckets=512)
    ans = rag.answer(corpus.queries[0], max_new_tokens=4)
    assert ans.entities and ans.context and len(ans.output_ids) == 4
    assert "upward hierarchical relationship" in ans.context or \
           "downward hierarchical relationship" in ans.context
    acc = rag.retrieval_accuracy(corpus.queries, corpus.query_entities)
    assert acc == 1.0                              # paper: same Acc as naive


def test_rag_device_lookup_path_matches_host():
    corpus = hospital_corpus(num_trees=10, num_queries=4)
    rag_h = RAGPipeline(corpus, None, tokenizer=HashTokenizer(1024),
                        num_buckets=512)
    rag_d = RAGPipeline(corpus, None, tokenizer=HashTokenizer(1024),
                        num_buckets=512, use_device_lookup=True)
    for q in corpus.queries:
        a = rag_h.retrieve(q)
        b = rag_d.retrieve(q)
        assert a.entities == b.entities
        # same entities mentioned in both context renderings
        for e in a.entities:
            assert (e in a.context) == (e in b.context)


def test_engine_tree_routed_retrieval():
    """Engine serves (tree_id, hash) query batches against a bank state."""
    from repro.core import CFTDeviceState, build_bank, build_forest
    from repro.core import hashing
    corpus = hospital_corpus(num_trees=8)
    forest = build_forest(corpus.trees)
    bank = build_bank(forest)
    _, eng = _engine()
    eng.attach_retrieval(CFTDeviceState.from_bank(bank, forest),
                         max_locs=4, batch_pad=32)
    hashes = hashing.hash_entities(forest.entity_names)
    tree_ids = bank.row_tree[:48].tolist()
    qh = [int(hashes[int(e)]) for e in bank.row_entity[:48]]
    out = eng.retrieve(tree_ids, qh)
    assert out.hit.shape == (48,) and bool(out.hit.all())
    for r in range(48):
        got = [int(v) for v in np.asarray(out.locations[r]) if v >= 0]
        assert got == bank.walk_row(r)[:4]
    # temperature threads back into engine state across calls
    t0 = int(np.asarray(out.temperature).sum())
    out2 = eng.retrieve(tree_ids, qh)
    assert int(np.asarray(out2.temperature).sum()) >= t0 + 48


def test_rag_bank_mode_scoped_and_global():
    corpus = hospital_corpus(num_trees=8, num_queries=4)
    rag = RAGPipeline(corpus, None, tokenizer=HashTokenizer(1024),
                      use_bank=True)
    host = RAGPipeline(corpus, None, tokenizer=HashTokenizer(1024))
    for q in corpus.queries:
        a = host.retrieve(q)
        b = rag.retrieve(q)                      # global: fan out over trees
        assert a.entities == b.entities
        for e in a.entities:
            assert (e in a.context) == (e in b.context)
        scoped = rag.retrieve(q, tree_scope=0)   # routed to one tree
        assert scoped.entities == a.entities


def test_repeated_eager_retrieve_compiles_nothing():
    """After one warm-up call, an eager retrieve with the same entity
    count (so the same shapes) compiles no program: the hierarchy walks
    are one module-level jit, not programs built per call."""
    from repro.obs import finished_spans, get_registry
    corpus = hospital_corpus(num_trees=6, num_queries=4)
    rag = RAGPipeline(corpus, None, tokenizer=HashTokenizer(1024),
                      use_bank=True)
    names = rag.forest.entity_names
    first = rag.retrieve(f"Where do {names[5]} and {names[9]} report?")
    compiles = get_registry().counter("xla.compiles")
    before = compiles.value()
    second = rag.retrieve(f"Where do {names[7]} and {names[12]} report?")
    assert len(first.entities) == len(second.entities) == 2
    assert second.context
    assert compiles.value() == before
    span, = finished_spans("rag.retrieve", 1)
    assert span["attrs"].get("compile_s", 0.0) == 0.0


def test_kv_cache_sizing():
    cfg = get_arch("yi-34b")
    by = kv_cache_bytes(cfg, batch=128, cache_size=32768)
    # 2 * 60L * 128B * 8kv * 32768 * 128hd * 2bytes
    assert by == 2 * 60 * 128 * 8 * 32768 * 128 * 2


@pytest.mark.parametrize("arch", ["zamba2-7b", "qwen2-0.5b"])
def test_kv_cache_bytes_match_the_decode_state(arch):
    """The sizing helper counts what ``init_decode_state`` holds: for the
    hybrid, a KV cache per shared-block call (224-wide heads) and conv
    and SSM state per layer; the engine's gauge holds one sequence's."""
    from repro.models import lm
    from repro.obs import get_registry
    from repro.serving.engine import decode_state_bytes
    cfg = get_arch(arch).replace(n_layers=24, hybrid_layers=(6, 11, 17, 23)) \
        if arch == "zamba2-7b" else get_arch(arch)
    shapes = jax.eval_shape(lambda: lm.init_decode_state(cfg, None, 4, 2048))
    held = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(shapes)
               if l.ndim)
    assert kv_cache_bytes(cfg, batch=4, cache_size=2048) == held
    kinds = decode_state_bytes(cfg, 2048)
    assert sum(kinds.values()) * 4 == held
    if arch == "zamba2-7b":
        assert set(kinds) == {"kv", "ssm", "conv"}
        # 4 calls x (k, v) x 32 heads x 2048 x 224 in bf16, a sequence,
        # each head's 224 lanes held in two whole rows of 128
        assert kinds["kv"] == 4 * 2 * 32 * 2048 * 256 * 2
        # 24 layers x 112 heads x 64 x 64 in fp32
        assert kinds["ssm"] == 24 * 112 * 64 * 64 * 4
        ServeEngine(cfg.smoke(), None, cache_size=64)
        gauge = get_registry().gauge("serve.state_bytes")
        small = decode_state_bytes(cfg.smoke(), 64)
        assert {k: gauge.value(kind=k) for k in small} == small


def _stepwise(cfg, params, toks, cache, max_new):
    """Greedy tokens one step at a time: the prefill, then a dispatch of
    ``decode_step`` and ``greedy_token`` per step, each token on the
    host before the next step."""
    from repro.models import lm
    logits, state = jax.jit(lambda p, b: lm.prefill(cfg, p, b, cache))(
        params, {"tokens": toks})
    step = jax.jit(lambda p, t, s: lm.decode_step(cfg, p, t, s))
    tok = lm.greedy_token(logits)
    out = [np.asarray(tok)]
    for _ in range(max_new - 1):
        logits, state = step(params, tok, state)
        tok = lm.greedy_token(logits)
        out.append(np.asarray(tok))
    return np.concatenate(out, axis=1)


@pytest.mark.parametrize("max_new", [1, 2, 7])
@pytest.mark.parametrize("arch", ["qwen2-0.5b", "zamba2-7b", "rwkv6-1.6b"])
def test_generate_matches_stepwise_decode(arch, max_new):
    """The on-device decode loop serves, token for token, what the
    step-by-step loop serves: the same greedy argmax over the same
    ``decode_step``, the same number of steps."""
    cfg = get_arch(arch).smoke()
    params = init_params(cfg, jax.random.PRNGKey(1))
    toks = jnp.asarray(np.random.default_rng(2).integers(
        4, cfg.vocab, (2, 12)), jnp.int32)
    eng = ServeEngine(cfg, params, cache_size=32, batch_size=2)
    out = eng.generate({"tokens": toks}, max_new_tokens=max_new)
    assert out.shape == (2, max_new) and out.dtype == np.int32
    np.testing.assert_array_equal(
        out, _stepwise(cfg, params, toks, 32, max_new))


def test_decode_loop_compiles_once_and_dispatches_once_an_answer():
    """After the first answer, answers of other lengths at the same
    shapes compile nothing (the trip count is traced), and each answer
    of two or more tokens launches one decode program."""
    from repro.obs import finished_spans, get_registry
    cfg, eng = _engine(cache=32)
    toks = jnp.asarray(np.random.default_rng(3).integers(
        4, cfg.vocab, (2, 10)), jnp.int32)
    eng.generate({"tokens": toks}, max_new_tokens=3)
    reg = get_registry()
    compiles = reg.counter("xla.compiles")
    dispatches = reg.counter("serve.decode_dispatches")
    for n in (9, 1, 2, 17):
        c0, d0 = compiles.value(), dispatches.value()
        assert eng.generate({"tokens": toks}, n).shape == (2, n)
        assert compiles.value() == c0
        assert dispatches.value() == d0 + (n > 1)
        span, = finished_spans("serve.generate", 1)
        assert span["attrs"]["steps"] == n
        assert span["attrs"]["dispatches"] == int(n > 1)
        assert [s["stage"] for s in span["stages"]] == ["prefill", "decode"]
    with pytest.raises(ValueError):
        eng.generate({"tokens": toks}, 34)
