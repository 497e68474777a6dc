"""Kernel microbenchmarks: per-kernel error against its jnp oracle.

Wall time on the CPU is interpret-mode and says nothing about the TPU
target, so each row's ``derived`` column reports max |err| against the
oracle (1 = exact for the probe); ``work`` is a size figure of the case.

``python -m benchmarks.bench_kernels [--json BENCH_kernels.json]``
"""
from __future__ import annotations

import sys

import numpy as np
import jax.numpy as jnp

from benchmarks.common import parse_bench_args, write_json


def micro_rows():
    """Per-kernel oracle deltas (the old print-only bench): wall time on
    CPU is interpret-mode and not meaningful for the TPU target, so the
    derived column reports max |err| vs the jnp oracle (1 = exact)."""
    rng = np.random.default_rng(0)
    rows = []

    # cuckoo lookup: exactness + table footprint
    from repro.core import build_forest, build_index, lookup_arena
    from repro.core import hashing
    from repro.kernels.cuckoo_lookup import cuckoo_lookup_arena
    forest = build_forest([[(f"r{t}", f"e{t}_{i}") for i in range(8)]
                           for t in range(80)])
    idx = build_index(forest, num_buckets=1024)
    t = idx.filter.tables()
    fps, heads = jnp.asarray(t.fingerprints), jnp.asarray(t.heads)
    h = jnp.asarray(hashing.hash_entities(
        [forest.entity_names[i % forest.num_entities] for i in range(256)]))
    # the single filter is an arena of one segment
    args = (fps, heads, jnp.zeros((256,), jnp.int32),
            jnp.full((256,), 1023, jnp.uint32), h)
    ref = lookup_arena(*args)
    ker = cuckoo_lookup_arena(*args, interpret=True)
    exact = int(np.array_equal(np.asarray(ref.head), np.asarray(ker.head)))
    vmem_kib = t.fingerprints.size * 4 * 2 / 1024
    rows.append(dict(name="cuckoo_lookup/exact", work=vmem_kib,
                     derived=float(exact)))

    # flash attention: fwd error at a training-relevant tile
    from repro.kernels.flash_attention import attention_ref, flash_attention
    q = jnp.asarray(rng.normal(size=(1, 8, 512, 128)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 512, 128)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 512, 128)), jnp.bfloat16)
    err = float(jnp.max(jnp.abs(
        flash_attention(q, k, v, True, None, True).astype(jnp.float32)
        - attention_ref(q, k, v, causal=True).astype(jnp.float32))))
    flops = 4 * 1 * 8 * 512 * 512 * 128 / 2
    rows.append(dict(name="flash_attention/bf16_err", work=flops / 1e6,
                     derived=err))

    # decode attention: GQA-grouped split-KV
    from repro.kernels.decode_attention import (decode_attention,
                                                decode_attention_ref)
    qd = jnp.asarray(rng.normal(size=(4, 8, 128)), jnp.float32)
    kd = jnp.asarray(rng.normal(size=(4, 2, 2048, 128)), jnp.float32)
    vd = jnp.asarray(rng.normal(size=(4, 2, 2048, 128)), jnp.float32)
    lens = jnp.asarray([2048, 1500, 700, 1], jnp.int32)
    errd = float(jnp.max(jnp.abs(
        decode_attention(qd, kd, vd, lens, interpret=True)
        - decode_attention_ref(qd, kd, vd, lens))))
    rows.append(dict(name="decode_attention/f32_err",
                     work=4 * 8 * 2048 * 128 * 4 / 1e6, derived=errd))

    # linear scan: strong-decay regime
    from repro.kernels.linear_scan import linear_scan, linear_scan_ref
    qs = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    ks = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    vs = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    gs = jnp.asarray(-np.abs(rng.normal(size=(1, 4, 256, 64))) * 5.0,
                     jnp.float32)
    ok, sk = linear_scan(qs, ks, vs, gs, None, inclusive=False,
                         interpret=True)
    orf, srf = linear_scan_ref(qs, ks, vs, gs, None, inclusive=False)
    errs = float(jnp.max(jnp.abs(ok - orf)))
    rows.append(dict(name="linear_scan/strong_decay_err",
                     work=256 * 64 * 64 * 4 / 1e6, derived=errs))
    return rows


def main(argv=None) -> int:
    from repro.obs import get_registry
    _, json_path = parse_bench_args(
        sys.argv[1:] if argv is None else argv, "bench_kernels", flags=())
    micro = micro_rows()
    print("kernel microbenchmarks (derived = max|err| vs oracle, 1=exact)")
    for r in micro:
        print(f"  {r['name']:34s} work~{r['work']:10.1f}  "
              f"derived {r['derived']:.3e}")

    write_json(json_path, {"micro": micro,
                           "obs": get_registry().snapshot()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
