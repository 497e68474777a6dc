"""Replicated vs bank-axis-sharded lookup — the scaling claim, measured.

The replicated path keeps the whole ragged bucket arena on one device and
probes it with ``lookup_arena``, each query routed to its tree's segment;
the sharded path partitions tree
ranges over the mesh (``FilterBank.shard`` + ``stage_sharded_bank``) and
routes each query batch through the ``shard_map`` all-to-all
(``sharded_lookup_bank``).  For every T the sweep records wall-clock for
both, the per-device filter-table bytes for both (the capacity axis the
sharding actually buys), and gates on bit-identical results before any
timing is reported.

Run on a forced multi-device host platform::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m benchmarks.bench_distributed \\
        [--smoke] [--json BENCH_shard.json]

The CI smoke job writes ``BENCH_shard.json`` from here (next to
``BENCH_bank.json`` from ``bench_churn``) so the distributed-lookup perf
trajectory is recorded per commit.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from repro.core import build_bank, lookup_arena
from repro.core import hashing

from .common import (best_time, parse_bench_args, synthetic_forest,
                     write_json)


def _queries(forest, bank, batch: int, seed: int):
    """Mixed hit/miss batch spread over every tree."""
    rng = np.random.default_rng(seed)
    t = bank.num_trees
    qt = rng.integers(0, t, size=batch).astype(np.int32)
    names = np.asarray(forest.entity_names)
    qh = np.empty(batch, np.uint32)
    for j in range(batch):
        if j % 4 == 0:                                   # 25% misses
            qh[j] = np.uint32(rng.integers(1, 2 ** 32))
        else:
            qh[j] = hashing.entity_hash(
                f"entity {qt[j]}_{rng.integers(len(names) // t)}")
    return qt, qh


def run(tree_counts: Sequence[int] = (16, 64, 256),
        entities_per_tree: int = 24, batch: int = 1024, iters: int = 5,
        seed: int = 0) -> List[Dict]:
    import jax
    import jax.numpy as jnp
    from repro.core.distributed import (stage_sharded_bank,
                                        sharded_lookup_bank)

    d = jax.device_count()
    mesh = jax.make_mesh((d,), ("model",))
    rows = []
    for t in tree_counts:
        forest = synthetic_forest(t, entities_per_tree)
        bank = build_bank(forest)
        sbank = bank.shard(d)
        state = stage_sharded_bank(sbank, forest, mesh, "model")
        qt, qh = _queries(forest, bank, batch, seed)
        qt_j, qh_j = jnp.asarray(qt), jnp.asarray(qh)

        mf, mt, mh = sbank.merged_tables()
        moff, mnb = sbank.merged_layout()
        fps_r, heads_r = jnp.asarray(mf), jnp.asarray(mh)
        off_r = jnp.asarray(moff.astype(np.int32))
        nb_r = jnp.asarray(mnb)

        @jax.jit
        def rep_fn(fps, heads, offsets, tree_nb, qt, qh):
            return lookup_arena(fps, heads, offsets[qt],
                                (tree_nb[qt] - 1).astype(jnp.uint32), qh)

        # ---- equivalence gate before timing
        ref = rep_fn(fps_r, heads_r, off_r, nb_r, qt_j, qh_j)
        got = sharded_lookup_bank(state, qt_j, qh_j)
        for f in ("hit", "head", "bucket", "slot"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ref, f)), np.asarray(getattr(got, f)),
                err_msg=f"sharded {f} diverged at T={t}")

        t_rep = best_time(
            lambda: jax.block_until_ready(
                rep_fn(fps_r, heads_r, off_r, nb_r, qt_j, qh_j)), iters)
        t_shd = best_time(
            lambda: jax.block_until_ready(
                sharded_lookup_bank(state, qt_j, qh_j)), iters)

        rep_dev = sum(int(jnp.asarray(x).nbytes) for x in (mf, mt, mh))
        shard_dev = sum(
            next(iter(x.addressable_shards)).data.nbytes
            for x in (state.fingerprints, state.temperature, state.heads))
        rows.append(dict(
            trees=t, arena_rows=bank.total_buckets,
            max_tree_rows=int(bank.tree_nb.max()), slots=bank.slots,
            devices=d, batch=batch,
            replicated_ms=t_rep * 1e3, sharded_ms=t_shd * 1e3,
            speedup=t_rep / t_shd if t_shd else 0.0,
            replicated_device_bytes=rep_dev,
            sharded_device_bytes=shard_dev,
            bytes_fraction=shard_dev / rep_dev,
            hits=int(np.asarray(got.hit).sum()),
        ))
    return rows


def print_rows(rows: List[Dict]) -> None:
    print("distributed: replicated vs bank-axis sharded lookup "
          "(all-to-all routed, no bank broadcast)")
    print(f"{'trees':>6s} {'dev':>4s} {'batch':>6s} {'rep_ms':>9s} "
          f"{'shard_ms':>9s} {'speedup':>8s} {'dev_bytes':>10s} "
          f"{'frac':>6s}")
    for r in rows:
        print(f"{r['trees']:6d} {r['devices']:4d} {r['batch']:6d} "
              f"{r['replicated_ms']:9.3f} {r['sharded_ms']:9.3f} "
              f"{r['speedup']:8.2f} {r['sharded_device_bytes']:10d} "
              f"{r['bytes_fraction']:6.3f}")


def main() -> None:
    import sys
    flags, json_path = parse_bench_args(sys.argv[1:], "bench_distributed",
                                        flags=("--smoke",))
    kw = (dict(tree_counts=(16, 64), entities_per_tree=12, batch=256,
               iters=2)
          if "--smoke" in flags else
          dict(tree_counts=(16, 64, 256), entities_per_tree=24,
               batch=1024, iters=5))
    import jax
    rows = run(**kw)
    print_rows(rows)
    for r in rows:
        # the capacity claim: per-device table bytes shrink ~1/D.  The
        # packed ragged layout pads every shard to the largest shard's
        # arena, and a contiguous tree partition can misplace at most
        # about one tree's worth of rows — so the honest bound is
        # 1/D + (largest tree segment)/A, tight as T grows.
        bound = (1.0 / r["devices"]
                 + r["max_tree_rows"] / r["arena_rows"] + 0.02)
        assert r["bytes_fraction"] <= bound, (r, bound)
    write_json(json_path, {"device_count": jax.device_count(),
                           "rows": rows})


if __name__ == "__main__":
    main()
