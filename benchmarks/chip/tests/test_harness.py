"""The benchmark's shape: every cell and metric resolves to its files,
names and units keep to their characters, traffic follows its seed, the
work counts follow shapes alone, and the command refuses to run without
a TPU."""
from __future__ import annotations

import collections
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

import counts
import harness
from entries.common import dataset, generator
from reference.forest import Forest, poisson_upper

BENCH = harness.spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _cells():
    return [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(harness.ROOT, p))
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024


def test_names_and_units():
    names = [c["name"] for c in BENCH["configs"]] + _cells() + [
        m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("cell", _cells())
def test_cell_resolves_to_its_files(cell):
    w, config, traffic, e2e, layer = harness.resolve(cell)
    assert os.path.isfile(os.path.join(harness.HERE, "entries",
                                       traffic["entry"] + ".py"))
    assert hasattr(generator(traffic), "generate")
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    assert layer
    for m in layer:
        reader = harness.load_module(os.path.join(
            harness.HERE, "metrics", m["name"] + ".py"))
        assert callable(reader.read)
        # a per-layer metric moves an end-to-end metric its cell reports
        assert m["moves"] in names


def test_every_config_used_and_filed():
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"]
        assert body["reduced"] == c["reduced"]


def test_peaks_name_their_source():
    with open(os.path.join(harness.HERE, "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["source"]
    v5e = peaks["kinds"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def _forest(trees=12):
    from repro.data import hospital_corpus
    return Forest.from_edges(hospital_corpus(num_trees=trees).trees)


@pytest.mark.parametrize("mix", ["scoped-zipf-poisson", "answer-pool32",
                                 "fanout-zipf-k5"])
def test_traffic_follows_the_seed(mix):
    with open(os.path.join(harness.HERE, "traffic", mix + ".json")) as f:
        params = json.load(f)
    gen = generator(params)
    forest = _forest()
    size = 4.0 if "rate_per_s" in params else 64
    big = 2 ** 31 + 12345

    def flat(s):
        return json.dumps({k: (v.tolist() if isinstance(v, np.ndarray)
                               else [np.asarray(x).tolist() for x in v]
                               if isinstance(v, list) and v
                               and isinstance(v[0], np.ndarray) else v)
                           for k, v in vars(s).items()}, default=str)

    a, b, c = (gen.generate(forest, params, size, s) for s in (big, big, 7))
    assert flat(a) == flat(b)
    assert flat(a) != flat(c)


# the first 2,048 queries of each closed-loop mix as they were drawn before
# the schedules grew in blocks: sha256 of their JSON, first 16 digits
FIRST_BLOCK = {
    ("retrieve-fanout-600", 7): "ec4da3ec2b69b50b",
    ("retrieve-fanout-600", 2 ** 31 + 12345): "6d6a30963a39f764",
    ("retrieve-fanout-600", 3140021900): "825370e8727c768e",
    ("rag-answer-600", 7): "14af9d241807038d",
    ("rag-answer-600", 2 ** 31 + 12345): "a3917d386730bd54",
    ("rag-answer-600", 3140021900): "3cd282b3c89c6e26",
}


def _digest(queries, entities, max_new):
    body = [queries, entities, None if max_new is None
            else [int(m) for m in max_new]]
    return hashlib.sha256(json.dumps(body).encode()).hexdigest()[:16]


@pytest.mark.parametrize("cell,seed", sorted(FIRST_BLOCK))
def test_extended_schedule_keeps_its_first_block(cell, seed):
    """The closed loops' schedules grow a block at a time: the first is
    the schedule of ``schedule_length`` queries as it always was, and the
    next follows the same laws with draws of its own."""
    w, config, traffic, _, _ = harness.resolve(cell)
    _, ref = dataset(config)
    entry = harness.load_module(os.path.join(
        harness.HERE, "entries", traffic["entry"] + ".py")).Entry(
        harness.Context(cell=w, config=config, traffic=traffic, seed=seed,
                        seconds=1.0, trace=False))
    entry.ref = ref
    n = traffic["schedule_length"]
    s = entry.block(0)
    s.extend(entry.block(1))
    assert len(s.queries) == len(s.entities) == 2 * n
    head = [s.queries[:n], s.entities[:n],
            None if s.max_new is None else s.max_new[:n]]
    assert _digest(*head) == FIRST_BLOCK[cell, seed]
    base = generator(traffic).generate(ref, traffic, n, seed)
    assert _digest(*head) == _digest(base.queries, base.entities,
                                     base.max_new)
    tail = s.queries[n:]
    assert tail != s.queries[:n]
    assert all(len(e) == traffic["entities_per_query"]
               for e in s.entities[n:])
    if "pool_size" in traffic:
        # whole periods of the pool: each query as often as in the first
        assert collections.Counter(tail) == collections.Counter(
            s.queries[:n])
        assert sorted(s.max_new[n:]) == sorted(s.max_new[:n])
    else:
        assert not set(tail) & set(s.queries[:n])


def test_open_loop_offers_the_same_load_on_every_seed():
    with open(os.path.join(harness.HERE, "traffic",
                           "scoped-zipf-poisson.json")) as f:
        params = json.load(f)
    gen = generator(params)
    forest = _forest()
    sizes = []
    for seed in (1, 2, 3):
        s = gen.generate(forest, params, 5.0, seed)
        assert s.offsets.size == round(params["rate_per_s"] * 5.0)
        assert np.all(np.diff(s.offsets) >= 0) and s.offsets[-1] < 5.0
        sizes.append(sorted(len(h) for h in s.hashes))
    assert sizes[0] == sizes[1] == sizes[2]


def test_absent_pairs_are_absent():
    with open(os.path.join(harness.HERE, "traffic",
                           "scoped-zipf-poisson.json")) as f:
        params = json.load(f)
    forest = _forest()
    s = generator(params).generate(forest, params, 5.0, 11)
    trees = np.concatenate(s.trees).astype(np.int64)
    hashes = np.concatenate(s.hashes).astype(np.int64)
    held = np.isin((trees << 32) | hashes, forest.node_keys())
    assert abs((~held).mean() - params["absent_share"]) < 0.01


def test_counts_follow_shapes_not_the_arena():
    """Both configurations share the bank's shape (slots, locations,
    walk length) but not its size: the per-query bytes are equal."""
    per = []
    for name in ("hospital600-qwen2-0.5b", "hospital6k-bank"):
        cfg = next(c for c in BENCH["configs"] if c["name"] == name)
        with open(os.path.join(harness.ROOT, cfg["file"])) as f:
            bank = json.load(f)["bank"]
        per.append([counts.retrieval_bytes(bank["slots"], bank["max_locs"],
                                           bank["hierarchy_n"], hit)
                    for hit in (False, True)]
                   + [counts.probe_kernel_bytes(bank["slots"], hit)
                      for hit in (False, True)])
    assert per[0] == per[1]
    # a miss reads both candidate buckets' fingerprints and writes its
    # outputs; a hit also reads the payload, bump, CSR window and walks
    s, locs, n = 4, 4, 3
    assert counts.probe_kernel_bytes(s, False) == 4 * (3 + 2 * s + 4)
    assert counts.retrieval_bytes(s, locs, n, True) \
        - counts.retrieval_bytes(s, locs, n, False) == 4 * (1 + 2 + 3 + 6 * n)


def test_qwen2_flops_per_token_closed_form():
    with open(os.path.join(harness.HERE, "configs",
                           "hospital600-qwen2-0.5b.json")) as f:
        model = json.load(f)["model"]
    d, ff, layers, vocab = 896, 4864, 24, 151936
    q, kv, hd, heads = 896 * 896, 896 * 128, 64, 14
    per_layer = 2 * q + 2 * kv + 3 * d * ff      # q, o; k, v; gate, up, down
    assert per_layer == 14_909_440
    for ctx in (1, 300, 512):
        want = layers * (2 * per_layer + 4 * heads * hd * ctx) + 2 * d * vocab
        assert counts.decoder_token_flops(model, ctx, head=True) == want
    # an answer: prompt tokens without logits but the last, then decodes
    p, new = 5, 3
    want = sum(counts.decoder_token_flops(model, i + 1, head=(i == p - 1))
               for i in range(p)) + sum(
        counts.decoder_token_flops(model, p + j + 1, head=True)
        for j in range(new - 1))
    assert counts.answer_flops(model, p, new) == want


def test_poisson_upper():
    assert poisson_upper(0) == 0
    # P(X > 19) for Poisson(5) is about 3.5e-7, P(X > 18) about 1.4e-6
    assert poisson_upper(5.0) == 19
    assert poisson_upper(5000.0) > 5000 + 4 * 70


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", str(2 ** 31 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return not any(ln.lstrip().startswith("{") for ln in lines)


def test_command_refuses_without_a_tpu(tmp_path):
    proc = _run(harness.ROOT, {"JAX_COMPILATION_CACHE_DIR":
                               str(tmp_path / "cache")})
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "TPU" in proc.stderr


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(harness.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path), {})
    assert proc.returncode != 0
    assert _no_result(proc)
