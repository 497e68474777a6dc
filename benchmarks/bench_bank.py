"""Filter-bank benchmarks — the paper's trees-vs-speedup sweep (§4.5).

Two claims, measured over T in {1, 8, 64, 256}:

* build: the vectorized bulk path (batched hashing + grouped empty-slot
  placement across all trees at once) vs. inserting every (tree, entity)
  item through the scalar cuckoo path;
* lookup: one routed device lookup of every tree's queries at once (the
  serving shape: ``(tree_id, hash)`` pairs over the ragged arena) vs.
  looping the lookup per tree, each tree's segment probed as an arena of
  one segment — asserted exact-equal before timing, per the
  reproduction's acceptance bar.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core import build_bank, lookup_arena
from repro.core import hashing

from .common import best_time, synthetic_forest


def _best(fn, repeats: int) -> float:
    return best_time(fn, repeats, warmup=False)


def run(tree_counts: Sequence[int] = (1, 8, 64, 256),
        entities_per_tree: int = 48, batch_per_tree: int = 64,
        repeats: int = 3) -> List[Dict]:
    rows = []
    for T in tree_counts:
        forest = synthetic_forest(T, entities_per_tree)
        t_bulk = _best(lambda: build_bank(forest, bulk=True), repeats)
        t_seq = _best(lambda: build_bank(forest, bulk=False),
                      1 if T >= 64 else repeats)
        bank = build_bank(forest)

        names = [[f"entity {t}_{i % entities_per_tree}" if i % 8 else
                  f"missing {t}_{i}" for i in range(batch_per_tree)]
                 for t in range(T)]
        hb = np.stack([hashing.hash_entities(ns) for ns in names])  # (T, B)
        fps = jnp.asarray(bank.fingerprints)
        heads = jnp.asarray(bank.heads)
        tid = np.repeat(np.arange(T), batch_per_tree)
        # each query's segment start and bucket mask, as retrieve_device
        batch = (fps, heads,
                 jnp.asarray(bank.bucket_offsets[tid].astype(np.int32)),
                 jnp.asarray((bank.tree_nb[tid] - 1).astype(np.uint32)),
                 jnp.asarray(hb.reshape(-1)))
        zeros = jnp.zeros((batch_per_tree,), jnp.int32)
        per_tree = []
        for t in range(T):
            lo, hi = bank.segment(t)
            per_tree.append((fps[lo:hi], heads[lo:hi], zeros,
                             jnp.full((batch_per_tree,), hi - lo - 1,
                                      jnp.uint32), jnp.asarray(hb[t])))

        # exactness: one routed batch vs per-tree one-segment lookups
        got = lookup_arena(*batch)
        exact = True
        for t, args in enumerate(per_tree):
            ref = lookup_arena(*args)
            rows_t = slice(t * batch_per_tree, (t + 1) * batch_per_tree)
            exact &= bool(jnp.array_equal(got.hit[rows_t], ref.hit))
            exact &= bool(jnp.array_equal(got.head[rows_t], ref.head))

        t_batch = _best(lambda: lookup_arena(
            *batch).hit.block_until_ready(), repeats)

        def loop():
            for args in per_tree:
                lookup_arena(*args).hit.block_until_ready()
        loop()
        t_loop = _best(loop, repeats)

        rows.append(dict(
            trees=T, items=bank.num_rows, num_buckets=bank.num_buckets,
            build_bulk_s=t_bulk, build_seq_s=t_seq,
            build_speedup=t_seq / t_bulk,
            lookup_batch_s=t_batch, lookup_loop_s=t_loop,
            lookup_speedup=t_loop / t_batch if t_batch else 0.0,
            exact=exact,
            evicted=bank.build_stats["evicted"],
        ))
    return rows


def main() -> None:
    rows = run()
    print("bank build + lookup vs #trees "
          "(paper: gap widens with many trees)")
    print(f"{'trees':>6s} {'items':>7s} {'bulk_s':>10s} {'seq_s':>10s} "
          f"{'build_x':>8s} {'batch_s':>10s} {'loop_s':>10s} {'look_x':>7s} "
          f"{'exact':>6s}")
    for r in rows:
        print(f"{r['trees']:6d} {r['items']:7d} {r['build_bulk_s']:10.5f} "
              f"{r['build_seq_s']:10.5f} {r['build_speedup']:8.1f} "
              f"{r['lookup_batch_s']:10.5f} {r['lookup_loop_s']:10.5f} "
              f"{r['lookup_speedup']:7.1f} {str(r['exact']):>6s}")
        assert r["exact"], "batched lookup diverged from reference"


if __name__ == "__main__":
    main()
