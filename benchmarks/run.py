"""Benchmark entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus the human tables from
each module's main()).  ``python -m benchmarks.run [--fast|--smoke]``
(``--smoke`` is the CI-sized variant: tiny inputs, every harness exercised).
"""
from __future__ import annotations

import sys

from repro.launch.compile_cache import configure_compile_cache

from . import (bench_bank, bench_churn, bench_fig5, bench_filter,
               bench_kernels, bench_pause, bench_ragged, bench_serving,
               bench_table1, bench_table2)


def main() -> None:
    unknown = [a for a in sys.argv[1:] if a not in ("--fast", "--smoke")]
    if unknown:        # a typo'd flag must not silently run the full suite
        sys.exit(f"usage: python -m benchmarks.run [--fast|--smoke] "
                 f"(unknown: {' '.join(unknown)})")
    configure_compile_cache()
    smoke = "--smoke" in sys.argv
    fast = smoke or "--fast" in sys.argv
    csv = []

    tree_counts = ((12, 25) if smoke else
                   (50, 120) if fast else (50, 300, 600))
    rows = bench_table1.run(tree_counts=tree_counts)
    print("\n== Table 1: retrieval time vs #trees ==")
    print(f"{'trees':>6s} {'algo':>6s} {'time_s':>12s} {'speedup':>9s} "
          f"{'acc':>6s}")
    for r in rows:
        print(f"{r['trees']:6d} {r['algo']:>6s} {r['time_s']:12.6f} "
              f"{r['speedup_vs_naive']:9.1f} {r['acc']:6.3f}")
        csv.append((f"table1/trees{r['trees']}/{r['algo']}",
                    r["time_s"] * 1e6, r["speedup_vs_naive"]))

    ent_counts = (5,) if smoke else (5, 10) if fast else (5, 10, 20)
    rows = bench_table2.run(entity_counts=ent_counts,
                            num_trees=25 if smoke else
                            120 if fast else 600)
    print("\n== Table 2: retrieval time vs #entities per query ==")
    print(f"{'ents':>5s} {'algo':>6s} {'time_s':>12s} {'speedup':>9s} "
          f"{'acc':>6s}")
    for r in rows:
        print(f"{r['entities']:5d} {r['algo']:>6s} {r['time_s']:12.6f} "
              f"{r['speedup_vs_naive']:9.1f} {r['acc']:6.3f}")
        csv.append((f"table2/ents{r['entities']}/{r['algo']}",
                    r["time_s"] * 1e6, r["speedup_vs_naive"]))

    rows = bench_fig5.run(num_trees=20 if smoke else 60 if fast else 300,
                          rounds=2 if smoke else 4 if fast else 8)
    print("\n== Figure 5: temperature-sort ablation (per round) ==")
    print(f"{'round':>6s} {'unsorted_probes':>16s} {'sorted_probes':>14s} "
          f"{'gain':>6s}")
    nr = 2 if smoke else 4 if fast else 8
    for rnd in range(1, nr + 1):
        u = next(r for r in rows if not r["sorted"] and r["round"] == rnd)
        s = next(r for r in rows if r["sorted"] and r["round"] == rnd)
        gain = u["probes"] / s["probes"]
        print(f"{rnd:6d} {u['probes']:16d} {s['probes']:14d} {gain:6.2f}")
        csv.append((f"fig5/round{rnd}/sorted", s["time_s"] * 1e6, gain))

    er = bench_filter.error_rate(probes=2_000 if smoke else
                                 20_000 if fast else 100_000)
    print("\n== Filter: load factor / error rate ==")
    for k, v in er.items():
        print(f"  {k}: {v}")
    csv.append(("filter/error_rate", 0.0, er["false_positive_rate"]))
    csv.append(("filter/load_factor", 0.0, er["load_factor"]))

    bv = bench_filter.batched_vs_sequential(
        num_trees=20 if smoke else 60 if fast else 300,
        batch=128 if smoke else 256 if fast else 512)
    print("\n== Batched device lookup vs sequential host loop ==")
    for k, v in bv.items():
        print(f"  {k}: {v}")
    csv.append(("filter/batched_speedup", bv["vectorized_s"] * 1e6,
                bv["speedup"]))

    bank_trees = ((1, 4) if smoke else (1, 8, 64) if fast
                  else (1, 8, 64, 256))
    rows = bench_bank.run(tree_counts=bank_trees,
                          entities_per_tree=8 if smoke else 48,
                          batch_per_tree=16 if smoke else 64,
                          repeats=1 if smoke else 3)
    print("\n== Filter bank: bulk build + batched lookup vs #trees ==")
    print(f"{'trees':>6s} {'items':>7s} {'build_x':>8s} {'lookup_x':>9s} "
          f"{'exact':>6s}")
    for r in rows:
        assert r["exact"], "bank lookup diverged from reference"
        print(f"{r['trees']:6d} {r['items']:7d} {r['build_speedup']:8.1f} "
              f"{r['lookup_speedup']:9.1f} {str(r['exact']):>6s}")
        csv.append((f"bank/trees{r['trees']}/build",
                    r["build_bulk_s"] * 1e6, r["build_speedup"]))
        csv.append((f"bank/trees{r['trees']}/lookup",
                    r["lookup_batch_s"] * 1e6, r["lookup_speedup"]))

    churn_kw = (dict(tree_counts=(16,), entities_per_tree=24, ops=128,
                     batch=32) if smoke else
                dict(tree_counts=(16, 64), entities_per_tree=32, ops=512)
                if fast else
                dict(tree_counts=(16, 64, 256), ops=2048))
    rows = bench_churn.run(**churn_kw)
    print("\n== Churn: incremental bank maintenance vs full rebuild ==")
    bench_churn.print_rows(rows)
    for r in rows:
        assert r["equal"], "incremental bank diverged from fresh build"
        csv.append((f"churn/trees{r['trees']}/incremental",
                    r["inc_us_per_op"], r["speedup"]))
        csv.append((f"churn/trees{r['trees']}/rebuild",
                    r["rebuild_us_per_op"], 1.0))

    rows = bench_ragged.run(
        tree_counts=(64,) if fast else (64, 256),
        entities_per_tree=4 if smoke else 8,
        iters=1 if smoke else 3)
    print("\n== Ragged arena: bytes + tree-local expand vs dense ==")
    bench_ragged.print_rows(rows)
    for r in rows:
        assert r["equal"], "ragged lookup diverged from reference"
        csv.append((f"ragged/trees{r['trees']}/bytes_fraction",
                    0.0, r["bytes_fraction"]))
        csv.append((f"ragged/trees{r['trees']}/expand",
                    r["expand_tree_ms"] * 1e3, r["expand_speedup"]))

    rows = bench_pause.run(
        num_trees=96 if smoke else 192,
        entities_per_tree=24 if smoke else 48,
        cycles=3 if smoke else 5, batches_per_cycle=4,
        batch=96 if smoke else 160, use_mesh=False)
    print("\n== Zero-pause maintenance: sync vs double-buffered "
          "restage ==")
    bench_pause.print_rows(rows)
    for r in rows:
        assert r["equal"], "splice commit diverged from full restage"
        csv.append((f"pause/{r['layout']}/sync", r["sync_max_pause_ms"]
                    * 1e3, 1.0))
        csv.append((f"pause/{r['layout']}/double_buffered",
                    r["db_max_pause_ms"] * 1e3, r["pause_reduction"]))

    print("\n== Kernel microbenchmarks (vs jnp oracle) ==")
    for r in bench_kernels.micro_rows():
        print(f"  {r['name']:34s} work~{r['work']:10.1f}  "
              f"derived {r['derived']:.3e}")
        csv.append((f"kernels/{r['name']}", r["work"], r["derived"]))

    if not fast:
        rows = bench_serving.run()
        ret = sum(r["retrieval_ms"] for r in rows) / len(rows)
        gen = sum(r["generation_ms"] for r in rows) / len(rows)
        print("\n== Serving: retrieval vs generation latency ==")
        print(f"  mean retrieval {ret:.2f} ms, generation {gen:.1f} ms "
              f"({100 * ret / (ret + gen):.2f}% of latency)")
        csv.append(("serving/retrieval_fraction", ret * 1e3,
                    ret / (ret + gen)))
        rows = bench_serving.run_bank_sweep()
        print("\n== Serving vs #trees: retrieval fraction + upkeep ==")
        bench_serving.print_bank_sweep(rows)
        for r in rows:
            csv.append((f"serving/trees{r['trees']}/retrieval_fraction",
                        r["retrieval_ms"] * 1e3, r["retrieval_fraction"]))
            csv.append((f"serving/trees{r['trees']}/maint_speedup",
                        r["maint_inc_us_per_op"], r["maint_speedup"]))

    print("\nname,us_per_call,derived")
    for name, us, derived in csv:
        print(f"{name},{us:.2f},{derived:.4f}")


if __name__ == "__main__":
    main()
