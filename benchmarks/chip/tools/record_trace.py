"""Record a small profiler trace of the probe kernel on the chip, for
``testdata/`` and the trace reduction's test.

    python3 benchmarks/chip/tools/record_trace.py <out_dir>

Stages a 6-tree hospital bank, warms the session's retrieval step, then
traces three probe batches, each inside a ``bench/retrieve`` annotation
and each followed by a short host pause inside ``bench/pause``.  Prints
the trace's planes and the reduction's numbers, and leaves the
``.xplane.pb`` under ``<out_dir>``.
"""
from __future__ import annotations

import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    out = (argv or sys.argv[1:])[0]
    sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                       "src")]
    import jax
    import numpy as np
    import harness
    from repro.core import CFTDeviceState, build_bank, build_forest
    from repro.data import hospital_corpus
    from repro.kernels.cuckoo_lookup.ops import cuckoo_lookup_arena_auto
    from repro.serving import RetrievalSession
    from trace_reduce import annotation, describe, reduce_trace

    harness.configure_jax()
    harness.require_chips(1)
    forest = build_forest(hospital_corpus(num_trees=6).trees)
    session = RetrievalSession()
    session.attach(CFTDeviceState.from_bank(build_bank(forest), forest),
                   lookup_fn=cuckoo_lookup_arena_auto)
    rng = np.random.default_rng(0)
    tids = rng.integers(0, 6, 128)
    hashes = rng.integers(0, 2 ** 32, 128, dtype=np.uint64)
    np.asarray(session.retrieve(tids, hashes).hit)
    jax.profiler.start_trace(out)
    for _ in range(3):
        with annotation("retrieve", True):
            np.asarray(session.retrieve(tids, hashes).hit)
        with annotation("pause", True):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    print(describe(out))
    s = reduce_trace(out, kernels=(harness.PROBE_KERNEL,))
    print("busy_s", s.busy_s, "kernel_s", s.kernel_s,
          "kernel_events", s.kernel_events)
    print("top ops", s.top_ops())
    print("gaps", s.top_gaps())
    return 0


if __name__ == "__main__":
    sys.exit(main())
