"""Chip smoke test: the CFT-RAG serving path at the paper's scale on a TPU.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --four-chips        # bank sharded over 4 chips

One chip: the ``paper-cftrag`` generator at full width (random weights
from ``--seed``) behind ``RAGPipeline`` over the 600-tree hospital corpus
with the filter bank on device.  It answers queries end to end, serves
one ``(tree_id, hash)`` batch through the retrieval session unfused and
fused, applies a live insert and splice commit, and checks every result:
device retrieval against the host BFS oracle and the jnp reference, fused
against unfused bit for bit, the inserted entity found, greedy decoding
deterministic, and prefill logits against a float32 reference.

``--four-chips`` runs only the bank-axis sharded pipeline on a 4-chip
mesh and the one-chip replicated pipeline on the same queries, and checks
that their retrievals are bit-identical and that each chip holds its own
shard of the arena.

The script exits non-zero without a result line when JAX finds no TPU or
when any check fails.  The last line of its output is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Times printed
on the way are set-up figures of one run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
NUM_TREES = 600             # hospital_corpus / bench_table2 default
NUM_QUERIES = 3
MAX_NEW = 8
CACHE = 512


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def _import_repo():
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SmokeFailure(f"no repro package under {src}: run this script "
                           "from a checkout of the repository")
    sys.path.insert(0, src)
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()


def _require_tpu(count: int):
    import jax
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"JAX found no TPU (platform {devs[0].platform}); this smoke "
          "test runs only on the chip")
    check(len(devs) >= count, f"needs {count} TPU chips, found {len(devs)}")
    return devs


# ------------------------------------------------------------- retrieval

def _query_batch(forest, rng, num_hits: int, num_misses: int):
    """``(tree_id, hash)`` pairs: entities queried in a tree that holds
    them, plus random hashes that should miss, with the node ids the host
    forest says each query must locate."""
    import numpy as np
    from repro.core import hashing
    nodes = rng.choice(np.flatnonzero(forest.entity_id >= 0), num_hits,
                       replace=False)
    trees, hashes, expect = [], [], []
    for g in nodes:
        eid, t = int(forest.entity_id[g]), int(forest.tree_id[g])
        name = forest.entity_names[eid]
        in_tree = np.flatnonzero((forest.entity_id == eid)
                                 & (forest.tree_id == t))
        trees.append(t)
        hashes.append(int(hashing.entity_hash(name)))
        expect.append(sorted(int(x) for x in in_tree))
    for _ in range(num_misses):
        trees.append(int(rng.integers(forest.num_trees)))
        hashes.append(int(rng.integers(1, 2 ** 32)))
        expect.append(None)
    return trees, hashes, expect


def _check_against_forest(out, expect, max_locs: int) -> int:
    """Every queried entity hits and locates exactly its nodes in the
    tree (the first ``max_locs`` when it has more)."""
    import numpy as np
    hit = np.asarray(out.hit)
    locs = np.asarray(out.locations)
    checked = 0
    for i, want in enumerate(expect):
        if want is None:
            continue
        check(hit[i], f"query {i}: entity in its tree but the probe missed")
        got = sorted(int(x) for x in locs[i] if x >= 0)
        if len(want) <= max_locs:
            check(got == want, f"query {i}: located {got}, forest has {want}")
        else:
            check(set(got) <= set(want) and len(got) == max_locs,
                  f"query {i}: located {got}, forest has {want}")
        checked += 1
    return checked


def _same(a, b, fields, what: str) -> None:
    import numpy as np
    for f in fields:
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        check(x.shape == y.shape and np.array_equal(x, y),
              f"{what}: field {f} differs")


def _lowers_to_mosaic(fn, *args) -> bool:
    """Whether ``fn`` compiles to a Mosaic kernel (interpret mode would
    compile to plain XLA loops instead)."""
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


# ------------------------------------------------------------- one chip

def smoke_one_chip(seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_arch
    from repro.core import (CFTDeviceState, DeviceRetrieval, NaiveTRAG,
                            hashing, retrieve_device)
    from repro.data import HashTokenizer, hospital_corpus
    from repro.data.ner import recognize_entities
    from repro.kernels.cuckoo_lookup.ops import cuckoo_lookup_arena_auto
    from repro.kernels.fused_retrieve import ops as fops
    from repro.models import init_params, prefill
    from repro.obs import get_registry
    from repro.serving import RAGPipeline, ServeEngine

    fields = DeviceRetrieval._fields
    t0 = time.perf_counter()
    cfg = get_arch("paper-cftrag")
    params = init_params(cfg, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    nparams = sum(int(x.size) for x in jax.tree.leaves(params))
    log(f"generator: {cfg.arch_id} layers={cfg.n_layers} d={cfg.d_model} "
        f"params={nparams} dtype={cfg.dtype}")
    engine = ServeEngine(cfg, params, cache_size=CACHE)
    corpus = hospital_corpus(num_trees=NUM_TREES)
    rag = RAGPipeline(corpus, engine, tokenizer=HashTokenizer(cfg.vocab),
                      use_bank=True)
    state = rag.session.state
    arena_rows = int(state.fingerprints.shape[0])
    log(f"bank: trees={rag.bank.num_trees} arena_rows={arena_rows} "
        f"slots={int(state.fingerprints.shape[1])} "
        f"forest_nodes={int(state.parent.shape[0])} "
        f"on={state.fingerprints.devices()}")
    setup_s = time.perf_counter() - t0
    log(f"set-up seconds (weights + bank build + staging): {setup_s:.3f}")

    # --- end-to-end answers: NER -> device probe -> context -> generate
    queries = corpus.queries[:NUM_QUERIES]
    req_s = []
    for q in queries:
        t = time.perf_counter()
        ans = rag.answer(q, max_new_tokens=MAX_NEW)
        req_s.append(time.perf_counter() - t)
        check(ans.output_ids is not None and len(ans.output_ids) == MAX_NEW,
              "answer produced no tokens")
        check(all(0 <= i < cfg.padded_vocab for i in ans.output_ids),
              f"token ids out of range: {ans.output_ids}")
        check(ans.context, f"no context retrieved for {q[:60]!r}")
    log("request seconds (first includes compiles): "
        + " ".join(f"{s:.3f}" for s in req_s))

    acc = rag.retrieval_accuracy(queries, corpus.query_entities[:NUM_QUERIES])
    log(f"retrieval_accuracy vs naive BFS: {acc}")
    check(acc == 1.0, f"retrieval accuracy {acc} != 1.0")

    # the device probe locates every queried entity exactly where the
    # host BFS finds it, in every tree that holds it; trees that do not
    # hold it may answer with a filter false positive, which is counted
    naive = NaiveTRAG(rag.forest)
    false_pos = probes = 0
    for q, gold in zip(queries, corpus.query_entities[:NUM_QUERIES]):
        names = recognize_entities(q, rag.gazetteer)
        check(set(gold) <= set(names), f"NER missed gold entities {gold}")
        trees, hashes, b = rag._device_query_batch(names)
        out = retrieve_device(rag.session.state, jnp.asarray(hashes),
                              jnp.asarray(trees),
                              lookup_fn=cuckoo_lookup_arena_auto)
        locs = np.asarray(out.locations).reshape(rag.bank.num_trees, b, -1)
        probes += len(trees)
        for j, e in enumerate(names):
            want = {}
            for t, g in naive.locate(e):
                want.setdefault(t, []).append(g)
            for t in range(rag.bank.num_trees):
                got = sorted(int(x) for x in locs[t, j] if x >= 0)
                if t not in want:
                    false_pos += bool(got)
                    continue
                w = sorted(want[t])
                check(got == w if len(w) <= 4 else set(got) <= set(w),
                      f"{e} in tree {t}: device located {got}, BFS {w}")
    log(f"device probe == BFS on every tree holding a queried entity; "
        f"filter false positives: {false_pos} of {probes} (tree, entity) "
        "probes")

    # --- one (tree_id, hash) batch through the session: unfused, fused,
    # and the jnp reference, each from a fresh copy of the bank's state
    rng = np.random.default_rng(seed)
    trees, hashes, expect = _query_batch(rag.forest, rng, 192, 64)
    fresh = lambda: CFTDeviceState.from_bank(rag.bank, rag.forest)  # noqa
    engine.attach_retrieval(fresh(), lookup_fn=cuckoo_lookup_arena_auto)
    hh, tid, _ = engine.retrieval.pad_queries(trees, hashes)
    check(_lowers_to_mosaic(engine.retrieval._step, engine.retrieval.state,
                            hh, tid),
          "unfused step did not compile to a Mosaic kernel")
    t = time.perf_counter()
    unfused = engine.retrieve(trees, hashes)
    jax.block_until_ready(unfused)
    unfused_s = time.perf_counter() - t
    reg = get_registry()
    fused_before = reg.counter("serve.fused_batches").value()
    engine.attach_retrieval(fresh(), fused=True)
    st = engine.retrieval.state
    plan = fops.launch_plan(arena_rows, int(st.fingerprints.shape[1]),
                            int(st.csr_offsets.shape[0]) - 1,
                            int(st.csr_nodes.shape[0]),
                            int(st.parent.shape[0]),
                            int(st.child_index.shape[0]))
    check(plan[0] is False and plan[1] is True,
          f"fused launch plan {plan} is not a compiled MXU kernel")
    t = time.perf_counter()
    fused = engine.retrieve(trees, hashes)
    jax.block_until_ready(fused)
    fused_s = time.perf_counter() - t
    fused_ran = reg.counter("serve.fused_batches").value() - fused_before
    check(fused_ran == 1, f"serve.fused_batches ticked {fused_ran} times")
    ref = retrieve_device(fresh(), hh, tid)
    b = len(hashes)
    ref = DeviceRetrieval(ref.hit[:b], ref.locations[:b], ref.up[:b],
                          ref.down[:b], ref.temperature)
    _same(unfused, fused, fields, "fused vs unfused")
    _same(unfused, ref, fields, "kernel vs jnp reference")
    n_checked = _check_against_forest(unfused, expect, 4)
    log(f"session batch: {b} queries ({n_checked} forest-checked hits), "
        f"fused == unfused == jnp reference on {', '.join(fields)}; "
        f"fused plan (interpret, mxu, row_tile, vmem_limit)={plan}; "
        f"seconds unfused {unfused_s:.3f} fused {fused_s:.3f} "
        "(first calls, compiles included)")

    # --- live insert + splice commit, then the device finds it
    tree = 7
    node = int(rag.forest.roots[tree])
    name = f"Radiology Annex Z{seed}"
    rag.insert_entity(tree, name, [node])
    report = rag.maintain()
    check(report is not None, "maintain() applied nothing")
    out = rag.session.retrieve([tree], [int(hashing.entity_hash(name))])
    check(bool(np.asarray(out.hit)[0]), f"inserted {name!r} not found")
    got = [int(x) for x in np.asarray(out.locations)[0] if x >= 0]
    check(got == [node], f"inserted {name!r} located {got}, want [{node}]")
    t = time.perf_counter()
    ans = rag.answer(f"What is the history of {name}?", max_new_tokens=MAX_NEW)
    req_s.append(time.perf_counter() - t)
    check(name in ans.entities, f"NER did not learn {name!r}")
    log(f"insert + commit: {name!r} -> node {node}; answered again in "
        f"{req_s[-1]:.3f} s")

    # --- generator: determinism and float32 reference
    ids = rag.tokenizer.encode(queries[0], bos=True)[:16]
    batch = {"tokens": jnp.asarray([ids] * engine.batch_size, jnp.int32)}
    g1 = engine.generate(batch, MAX_NEW)
    g2 = engine.generate(batch, MAX_NEW)
    check(np.array_equal(g1, g2), "greedy decoding is not deterministic")
    run = jax.jit(functools.partial(prefill, cfg, cache_size=CACHE))
    logits = np.asarray(run(params, batch)[0], np.float32)
    check(np.isfinite(logits).all(), "prefill logits are not finite")
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        ref32 = jax.jit(functools.partial(prefill, cfg32, cache_size=CACHE))
        want = np.asarray(ref32(p32, batch)[0], np.float32)
    diff = float(np.abs(logits - want).max())
    # bf16 activations against f32: the error grows about with the root of
    # the depth (0.037 at 2 layers, 0.053 at 6, rms(ref) ~ 1)
    bound = 0.05 * math.sqrt(cfg.n_layers) * float(np.sqrt((want ** 2).mean()))
    log(f"prefill logits vs float32 reference: max_abs_diff={diff:.6f} "
        f"bound={bound:.6f}")
    check(diff <= bound, f"logits differ by {diff} > {bound}")
    compiles = reg.snapshot()["histograms"].get("xla.compile_s", {})
    log(f"compile seconds (backend compiles after set-up): "
        f"{compiles.get('sum', 0.0):.3f} over {compiles.get('count', 0)} "
        "compiles")
    return {"arena_rows": arena_rows, "fused_ran": fused_ran}


# ------------------------------------------------------------ four chips

def smoke_four_chips(seed: int) -> None:
    import jax
    import numpy as np
    from repro.core import stage_sharded_bank
    from repro.data import hospital_corpus
    from repro.serving import RAGPipeline
    from repro.serving.engine import RetrievalSession

    fields = ("hit", "locations", "up", "down")
    corpus = hospital_corpus(num_trees=NUM_TREES)
    mesh = jax.make_mesh((4,), ("model",))
    t0 = time.perf_counter()
    rep = RAGPipeline(corpus, None, use_bank=True)
    shd = RAGPipeline(corpus, None, use_bank=True, mesh=mesh)
    log(f"set-up seconds (two bank builds + staging): "
        f"{time.perf_counter() - t0:.3f}")

    # each chip holds its own shard of the arena, not a replica
    st = shd.session.state
    fps_host = shd.bank.packed_tables()[0]
    rows = fps_host.shape[0] // 4
    shards = st.fingerprints.addressable_shards
    check(len({s.device.id for s in shards}) == 4,
          f"arena on {len(shards)} shards over "
          f"{len({s.device.id for s in shards})} devices, want 4")
    for s in shards:
        d = s.index[0].start // rows
        check(s.data.shape == (rows, fps_host.shape[1]),
              f"device {s.device.id} holds {s.data.shape}, want one shard")
        check(np.array_equal(np.asarray(s.data),
                             fps_host[d * rows:(d + 1) * rows]),
              f"device {s.device.id} does not hold shard {d}")
    owners = np.asarray(st.tree_shard)
    check(np.all(np.diff(owners) >= 0) and set(owners) == {0, 1, 2, 3},
          "trees are not partitioned into four contiguous ranges")
    log(f"sharded arena: 4 x {rows} rows on devices "
        f"{sorted(s.device.id for s in shards)}; tree ranges "
        f"{shd.bank.tree_starts.tolist()}")

    for q in corpus.queries[:NUM_QUERIES]:
        a, b = rep.retrieve(q), shd.retrieve(q)
        check(a.context == b.context, f"contexts differ for {q[:60]!r}")

    rng = np.random.default_rng(seed)
    trees, hashes, expect = _query_batch(rep.forest, rng, 192, 64)
    t = time.perf_counter()
    r = rep.session.retrieve(trees, hashes)
    s = shd.session.retrieve(trees, hashes)
    jax.block_until_ready((r, s))
    _same(r, s, fields, "sharded vs replicated")
    fused = RetrievalSession()
    fused.attach(stage_sharded_bank(shd.bank, shd.forest, mesh), fused=True)
    f = fused.retrieve(trees, hashes)
    _same(r, f, fields, "sharded fused vs replicated")
    n_checked = _check_against_forest(r, expect, 4)
    log(f"session batch: {len(hashes)} queries ({n_checked} forest-checked "
        f"hits); sharded (probe and fused) == replicated on "
        f"{', '.join(fields)}; {time.perf_counter() - t:.3f} s with compiles")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded-bank comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        _import_repo()
        devs = _require_tpu(4 if args.four_chips else 1)
        dev = devs[0]
        log(f"device: platform={dev.platform} kind={dev.device_kind} "
            f"count={len(devs)}")
        if args.four_chips:
            smoke_four_chips(args.seed)
        else:
            res = smoke_one_chip(args.seed)
            log(f"summary: kind={dev.device_kind} arena_rows="
                f"{res['arena_rows']} fused_plan_ran={res['fused_ran'] > 0}")
    except Exception as e:                       # any failed phase
        import traceback
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
