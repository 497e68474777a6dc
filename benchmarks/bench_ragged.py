"""Ragged arena vs dense pad-to-max — the hierarchical-skew claim, measured.

Real entity forests are skewed (one hot tree holding many times the
entities of its neighbours); the old dense ``(T, NB, S)`` bank padded
*every* tree to the hot tree's bucket count.  On a skewed forest (one tree
``hot_factor``x larger than the rest) this sweep records, per T:

* **bytes** — ragged arena device bytes (``sum nb_t`` rows) vs what the
  dense pad-to-max layout would pay (``T * max nb_t`` rows), three tables
  each;
* **expansion** — wall-clock of a single-tree ``expand_tree`` (restages
  only the hot tree's arena segment) vs a full-bank restage at doubled
  bucket counts (what the dense layout forced on any overflow);
* **equivalence gate** — host lookup, pure-jnp ragged lookup and the
  row-tiled Pallas arena kernel must answer bit-identically on a mixed
  hit/miss batch before any number is reported.

``python -m benchmarks.bench_ragged [--smoke] [--json BENCH_ragged.json]``
— the CI smoke job records ``BENCH_ragged.json`` next to
``BENCH_bank.json`` / ``BENCH_shard.json``.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from repro.core import (MaintenanceEngine, build_bank, build_forest,
                        lookup_arena)
from repro.core import hashing

from .common import best_time, parse_bench_args, write_json


def skewed_forest(num_trees: int, entities_per_tree: int,
                  hot_factor: int = 16, hot_tree: int = 0):
    """One-root trees where ``hot_tree`` holds ``hot_factor``x the
    entities of every other tree — the hierarchical-skew shape."""
    sizes = [entities_per_tree * (hot_factor if t == hot_tree else 1)
             for t in range(num_trees)]
    return build_forest(
        [[(f"root {t}", f"entity {t}_{i}") for i in range(sizes[t])]
         for t in range(num_trees)])


def _equivalence(bank, forest) -> bool:
    """Host vs jnp vs Pallas kernel, bit-identical (hits and misses on
    hit/head; bucket/slot on hits, as everywhere else in the suite)."""
    import jax.numpy as jnp
    from repro.kernels.cuckoo_lookup import cuckoo_lookup_arena

    hashes = hashing.hash_entities(forest.entity_names)
    tid = np.concatenate([bank.row_tree,
                          np.zeros(32, np.int32)]).astype(np.int32)
    hh = np.concatenate([hashes[bank.row_entity],
                         hashing.hash_entities([f"missing {i}"
                                                for i in range(32)])])
    # each query's segment start and bucket mask, as retrieve_device routes
    args = (jnp.asarray(bank.fingerprints), jnp.asarray(bank.heads),
            jnp.asarray(bank.bucket_offsets[tid].astype(np.int32)),
            jnp.asarray((bank.tree_nb[tid] - 1).astype(np.uint32)),
            jnp.asarray(hh))
    ref = lookup_arena(*args)
    ker = cuckoo_lookup_arena(*args, interpret=True)
    m = np.asarray(ref.hit)
    ok = (np.array_equal(np.asarray(ker.hit), m)
          and np.array_equal(np.asarray(ker.head), np.asarray(ref.head))
          and np.array_equal(np.asarray(ker.bucket)[m],
                             np.asarray(ref.bucket)[m])
          and np.array_equal(np.asarray(ker.slot)[m],
                             np.asarray(ref.slot)[m]))
    for r in range(0, bank.num_rows, max(1, bank.num_rows // 256)):
        t, e = int(bank.row_tree[r]), int(bank.row_entity[r])
        hit, row, _ = bank.lookup(t, int(hashes[e]))
        j = r                        # batch order == row order for hits
        ok &= hit and bool(m[j]) and int(np.asarray(ref.head)[j]) == row
    return bool(ok)


def run(tree_counts: Sequence[int] = (64, 256),
        entities_per_tree: int = 8, hot_factor: int = 16,
        iters: int = 3, seed: int = 0) -> List[Dict]:
    rows = []
    for t in tree_counts:
        forest = skewed_forest(t, entities_per_tree, hot_factor)
        bank = build_bank(forest)
        slot_bytes = bank.slots * 4 * 3          # fp + temp + heads tables
        dense_rows = t * int(bank.tree_nb.max())
        equal = _equivalence(bank, forest)

        def _expand_hot():
            eng = MaintenanceEngine(build_bank(forest), seed=seed)
            return lambda: eng.expand_tree(0, force=True)

        def _full_restage():
            eng = MaintenanceEngine(build_bank(forest), seed=seed)
            return lambda: eng.expand()

        t_tree = min(best_time(_expand_hot(), 1, warmup=False)
                     for _ in range(iters))
        t_full = min(best_time(_full_restage(), 1, warmup=False)
                     for _ in range(iters))

        rows.append(dict(
            trees=t, hot_factor=hot_factor,
            items=int(bank.num_items.sum()),
            arena_rows=bank.total_buckets, dense_rows=dense_rows,
            ragged_bytes=bank.total_buckets * slot_bytes,
            dense_bytes=dense_rows * slot_bytes,
            bytes_fraction=bank.total_buckets / dense_rows,
            expand_tree_ms=t_tree * 1e3, full_restage_ms=t_full * 1e3,
            expand_speedup=t_full / t_tree if t_tree else 0.0,
            equal=equal,
        ))
    return rows


def print_rows(rows: List[Dict]) -> None:
    print("ragged arena vs dense pad-to-max (skewed forest, "
          "one tree {}x larger)".format(rows[0]["hot_factor"] if rows
                                        else "?"))
    print(f"{'trees':>6s} {'items':>7s} {'arena':>7s} {'dense':>7s} "
          f"{'bytes%':>7s} {'tree_ms':>9s} {'full_ms':>9s} "
          f"{'exp_x':>6s} {'equal':>6s}")
    for r in rows:
        print(f"{r['trees']:6d} {r['items']:7d} {r['arena_rows']:7d} "
              f"{r['dense_rows']:7d} {100 * r['bytes_fraction']:6.1f}% "
              f"{r['expand_tree_ms']:9.3f} {r['full_restage_ms']:9.3f} "
              f"{r['expand_speedup']:6.1f} {str(r['equal']):>6s}")


def main() -> None:
    import sys
    flags, json_path = parse_bench_args(sys.argv[1:], "bench_ragged",
                                        flags=("--smoke",))
    # min-of-iters fresh-engine timings per side + retries: the expand
    # latency gate compares sub-millisecond wall clocks, so one scheduler
    # stall must never be able to fail CI
    kw = (dict(tree_counts=(64,), entities_per_tree=6, iters=5)
          if "--smoke" in flags else
          dict(tree_counts=(64, 256), entities_per_tree=8, iters=5))
    rows = run(**kw)
    for _ in range(2):              # retries: absorb CI scheduler noise
        if all(r["expand_speedup"] > 1.0 for r in rows):
            break
        rows = run(**kw)
    print_rows(rows)
    for r in rows:
        assert r["equal"], "ragged lookup diverged from reference"
        # the memory claim: arena bytes well under the dense pad-to-max
        assert r["ragged_bytes"] < 0.5 * r["dense_bytes"], r
        # the latency claim: one hot tree's expand beats a bank restage
        assert r["expand_speedup"] > 1.0, r
    write_json(json_path, {"rows": rows})


if __name__ == "__main__":
    main()
