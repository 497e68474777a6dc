"""The reduction from a profiler trace to the benchmark's device numbers:
on made-up planes whose answer is known, and on a small trace recorded
on a TPU v5e (``testdata/``, made by ``tools/record_trace.py``)."""
from __future__ import annotations

import os
from types import SimpleNamespace as NS

import pytest

import harness
from trace_reduce import idle_percent, reduce_planes, reduce_trace

TESTDATA = os.path.join(harness.HERE, "testdata", "probe_trace")


def _ev(name, start, dur, stats=()):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats))


def test_made_up_planes():
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        _ev("fusion.1", 0, 100),
        _ev("fusion.2", 50, 100),                  # overlaps: union 0..150
        _ev("custom-call", 400, 50, [("long_name", "cuckoo_probe.3")]),
        _ev("copy", 1000, 100)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench/answer", 100, 1000),
        _ev("bench/retrieve", 200, 150),           # holds the first gap
        _ev("unrelated", 0, 5000)])])
    s = reduce_planes([dev, host], kernels=("cuckoo_probe",))
    assert s.devices == 1
    assert s.busy_s == pytest.approx(300e-9)
    assert s.kernel_s["cuckoo_probe"] == pytest.approx(50e-9)
    assert s.kernel_events["cuckoo_probe"] == 1
    # gaps 150..400 (mid 275: inside retrieve) and 450..1000 (mid 725:
    # inside answer only)
    assert s.gaps == {"retrieve": pytest.approx(250e-9),
                      "answer": pytest.approx(550e-9)}
    assert s.top_ops(2)[0][0] in ("fusion.1", "fusion.2", "copy")
    assert idle_percent(s, 1100e-9) == pytest.approx(100 * (1 - 300 / 1100))
    assert idle_percent(reduce_planes([host]), 1.0) is None


def test_recorded_chip_trace():
    s = reduce_trace(TESTDATA, kernels=(harness.PROBE_KERNEL,))
    assert s.devices == 1
    # three probe batches, each a launch of the kernel by its stable name
    assert s.kernel_events[harness.PROBE_KERNEL] == 3
    assert 0 < s.kernel_s[harness.PROBE_KERNEL] <= s.busy_s
    assert s.busy_s < s.span_s
    assert set(s.gaps) <= {"retrieve", "pause", "host: none"}
    assert s.gaps.get("pause", 0) > 0
