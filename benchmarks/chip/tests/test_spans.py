"""The program's spans that the per-layer readers take are the window's.

A reader takes the newest ``n`` spans of a name, ``n`` being the
window's own count of calls or batches (``program_spans.py``).  That is
sound only while every such span of the timed path finishes inside the
window and nothing the entry does after it (``release``, ``verify``)
finishes another: checked here on the CPU at the harness tests' small
size, with the window's bounds on the spans' clock.
"""
from __future__ import annotations

import pytest

import harness
from program_spans import TAKEN, watched
from test_faults import SECONDS, SMALL

NAMES = ("rag.answer", "rag.retrieve", "serve.generate", "serve.batch")


@pytest.mark.parametrize("cell", sorted(TAKEN))
def test_readers_take_the_windows_spans(cell, monkeypatch, tmp_path):
    from repro.obs import finished_spans

    # a trace directory of its own: the harness's shared one may be
    # another worker's
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    _, _, _, _, layer = harness.resolve(cell)
    with watched(cell) as seen:
        res = harness.run_cell(cell, 2 ** 31 + 91, SECONDS.get(cell, 1.0),
                               True, require_tpu=False,
                               config_overrides=SMALL[cell])
    assert res["correct"], res["checks"]
    t0, t1 = seen["bounds"]
    name, count = TAKEN[cell]
    n = int(count(seen["window"]))
    assert n > 0
    taken = finished_spans(name, n)
    assert len(taken) == n
    assert all(t0 <= s["t0"] and s["t1"] <= t1 for s in taken)
    # the one before the newest n predates the window
    older = finished_spans(name, n + 1)
    assert len(older) == n or older[0]["t1"] < t0
    for other in NAMES:
        for s in finished_spans(other, 1):
            assert s["t1"] <= t1, (other, "ended after the window")
    # every reader of the cell that reads program spans gives a number
    programs = {m["name"] for m in layer
                if m["source"] != "device_trace"}
    assert programs <= set(res["metrics"])
