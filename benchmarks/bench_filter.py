"""Filter micro-benchmarks: load factor / error rate (paper §4.5.1 claims)
and batched device lookup vs sequential host lookup (TPU adaptation win)."""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.core import CuckooFilter, build_forest, build_index, lookup_arena
from repro.core import hashing
from repro.data import hospital_corpus
from repro.kernels.cuckoo_lookup import cuckoo_lookup_arena


def error_rate(num_entities: int = 3148, num_buckets: int = 1024,
               probes: int = 100_000):
    f = CuckooFilter(num_buckets=num_buckets)
    hs = hashing.hash_entities([f"entity {i}" for i in range(num_entities)])
    for i, h in enumerate(hs):
        f.insert(int(h), i, i)
    miss = hashing.hash_entities([f"absent {i}" for i in range(probes)])
    fp = sum(f.contains(int(h)) for h in miss)
    return {"load_factor": f.load_factor, "buckets": f.num_buckets,
            "false_positive_rate": fp / probes,
            "expansions": f.num_expansions}


def batched_vs_sequential(num_trees: int = 300, batch: int = 512,
                          repeats: int = 5):
    corpus = hospital_corpus(num_trees=num_trees)
    forest = build_forest(corpus.trees)
    idx = build_index(forest, num_buckets=1024)
    t = idx.filter.tables()
    fps, heads = jnp.asarray(t.fingerprints), jnp.asarray(t.heads)
    names = [forest.entity_names[i % forest.num_entities]
             for i in range(batch)]
    hs = hashing.hash_entities(names)
    # the single filter is an arena of one segment
    args = (fps, heads, jnp.zeros((batch,), jnp.int32),
            jnp.full((batch,), idx.filter.num_buckets - 1, jnp.uint32),
            jnp.asarray(hs))

    t0 = time.perf_counter()
    for _ in range(repeats):
        for h in hs:
            idx.filter.lookup(int(h), bump=False)
    t_seq = (time.perf_counter() - t0) / repeats

    lookup_arena(*args).hit.block_until_ready()   # warm
    t0 = time.perf_counter()
    for _ in range(repeats):
        lookup_arena(*args).hit.block_until_ready()
    t_vec = (time.perf_counter() - t0) / repeats

    out = cuckoo_lookup_arena(*args, interpret=True)
    out.hit.block_until_ready()
    t0 = time.perf_counter()
    cuckoo_lookup_arena(*args, interpret=True).hit.block_until_ready()
    t_kernel_interp = time.perf_counter() - t0

    return {"batch": batch, "sequential_s": t_seq, "vectorized_s": t_vec,
            "speedup": t_seq / t_vec,
            "pallas_interpret_s": t_kernel_interp}


def main():
    er = error_rate()
    print("filter: load factor / error rate (paper: 0.7686 load, ~0 errors)")
    for k, v in er.items():
        print(f"  {k}: {v}")
    bv = batched_vs_sequential()
    print("\nbatched lookup vs sequential host loop (TPU adaptation):")
    for k, v in bv.items():
        print(f"  {k}: {v}")


if __name__ == "__main__":
    main()
