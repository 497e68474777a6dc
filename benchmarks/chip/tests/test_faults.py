"""Each cell's comparison fails what it must fail.

The harness's look for a chip is skipped (``require_tpu=False``) and the
rest of a run is driven on the CPU at a small size: the forest cut to a
few dozen trees and the generator to two layers in float32 (where the
program and the reference agree to rounding, so the limit is tight).  A
clean run must come out correct; a run with the timed path broken
underneath (``entries/faults.py``) or with the control in the program's
place must not.
"""
from __future__ import annotations

import os
import re

import pytest

import harness

SMALL_MODEL = {
    "test_size": True,
    "model": {"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_hidden_layers": 2,
              "num_key_value_heads": 2, "vocab_size": 512,
              "torch_dtype": "float32"},
    "serving": {"cache_size": 256, "batch_size": 4},
    "checks": {"logit_gap": 1e-3},
}
SMALL = {
    "retrieve-scoped-6k": {"forest": {"num_trees": 60}},
    "retrieve-fanout-600": {"forest": {"num_trees": 30}},
    "rag-answer-600": dict(SMALL_MODEL, forest={"num_trees": 30}),
}
FAULTS = {
    "retrieve-scoped-6k": ["answer_altered", "half_batch", "short_walk"],
    "retrieve-fanout-600": ["answer_altered", "half_batch", "short_walk"],
    "rag-answer-600": ["answer_altered", "token_altered", "fp8_control"],
}


# the window compiles the pipeline's per-call programs for real, so an
# answer takes about a second here: the answer cell's window holds a few
SECONDS = {"rag-answer-600": 4.0}


def _run(cell, fault=None, trace=False, seconds=None, traffic=None):
    return harness.run_cell(cell, 2 ** 31 + 77,
                            seconds or SECONDS.get(cell, 1.0), trace,
                            require_tpu=False, config_overrides=SMALL[cell],
                            traffic_overrides=traffic, fault=fault)


def _entry(cell):
    """The module of the entry that the cell's window drives, as the
    harness loads it."""
    _, _, traffic, _, _ = harness.resolve(cell)
    return harness.load_module(os.path.join(harness.HERE, "entries",
                                            traffic["entry"] + ".py"))


def _note(res, pattern):
    return int(re.search(pattern, " ".join(res["notes"])).group(1))


def _break_from_call(monkeypatch, cell, first):
    """Plant faults only in the window's calls from the ``first`` on."""
    from entries import faults
    entry = _entry(cell)
    late = [False]
    call, brk = entry.Entry.call, faults._break

    def late_call(self, i):
        late[0] = i >= first
        call(self, i)
    monkeypatch.setattr(entry.Entry, "call", late_call)
    monkeypatch.setattr(faults, "_break",
                        lambda out, f: brk(out, f) if late[0] else out)


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_clean_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["device"]["count"] == 1 and res["device"]["kind"]


@pytest.mark.parametrize("cell,fault", [(c, f) for c in sorted(FAULTS)
                                        for f in FAULTS[c]])
def test_broken_path_is_not_correct(cell, fault):
    res = _run(cell, fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", ["retrieve-scoped-6k", "retrieve-fanout-600"])
def test_traced_run_reports_its_per_layer_metrics(cell):
    res = _run(cell, trace=True)
    _, _, _, _, layer = harness.resolve(cell)
    # off the chip there is no device plane: the device readers are
    # silent, the others read
    want = {m["name"] for m in layer if m["source"] != "device_trace"}
    assert not any(m["name"] in res["metrics"] for m in layer
                   if m["source"] == "device_trace")
    assert want <= set(res["metrics"])
    assert "breakdown" in res and "busy_s" in res["device"]


def test_window_loads_nothing_from_the_compile_cache(monkeypatch):
    """A program that set-up compiled, and wrote to the persistent cache
    (at a floor of 0 here; under JAX's 1 s floor, a compile that happened
    to be slow), is compiled again where the window needs it afresh, as
    in a deployment whose cache lacks it: here a fresh jitted function of
    the test's own in set-up and after every call."""
    import jax
    import jax.numpy as jnp
    from jax._src import compilation_cache

    def own():
        # a new function object each time: JAX looks its program up anew,
        # in memory and then in the persistent cache
        jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)))
    entry = _entry("retrieve-fanout-600")
    warm, call = entry.Entry.warm, entry.Entry.call
    monkeypatch.setattr(entry.Entry, "warm", lambda self: (warm(self), own()))
    monkeypatch.setattr(entry.Entry, "call",
                        lambda self, i: (call(self, i), own()))
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    try:
        res = _run("retrieve-fanout-600")
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
    assert res["correct"] and res["attempted"] > 0
    assert _note(res, r"window compiles (\d+)") >= res["attempted"]
    assert _note(res, r"persistent-cache loads (\d+)") == 0



@pytest.mark.parametrize("bound", ["configured", "engine_default"])
def test_scoped_stall_delays_instead_of_shedding(monkeypatch, bound):
    """A serving thread that stands still for a second while requests
    keep arriving: under the configuration's admission bound they wait
    and are answered, and the run is correct with nothing failed; under
    the engine's default bound (0.32 s of the cell's rate) they shed."""
    import time
    from repro.serving import RetrievalSession
    cell = "retrieve-scoped-6k"
    entry = _entry(cell)
    armed = [False]
    window, dispatch = entry.Entry.window, RetrievalSession.retrieve_dispatch

    def stalled_window(self, seconds):
        armed[0] = True
        return window(self, seconds)

    def stalled_dispatch(self, *a, **kw):
        if armed[0]:
            armed[0] = False
            time.sleep(1.0)
        return dispatch(self, *a, **kw)
    monkeypatch.setattr(entry.Entry, "window", stalled_window)
    monkeypatch.setattr(RetrievalSession, "retrieve_dispatch",
                        stalled_dispatch)
    over = dict(SMALL[cell])
    if bound == "engine_default":
        over["serving"] = {"max_queue_requests": 1024}
    res = harness.run_cell(cell, 2 ** 31 + 78, 2.0, False,
                           require_tpu=False, config_overrides=over)
    if bound == "configured":
        assert res["failed"] == 0 and res["correct"], res["checks"]
    else:
        assert res["failed"] > 0

# the closed loops' schedules cut to one query a block, so that every
# window outruns many blocks
ONE = {"schedule_length": 1}
LONG = {"retrieve-fanout-600": 2.0, "rag-answer-600": 4.0}


@pytest.mark.parametrize("cell", sorted(LONG))
def test_window_outruns_its_schedule(cell):
    res = _run(cell, seconds=LONG[cell], traffic=ONE)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 1 and res["failed"] == 0
    assert _note(res, r"schedule blocks (\d+)") == res["attempted"]


@pytest.mark.parametrize("cell,fault", [(c, f) for c in sorted(LONG)
                                        for f in ("answer_altered",
                                                  "short_walk")])
def test_faults_past_the_first_block_are_caught(monkeypatch, cell, fault):
    _break_from_call(monkeypatch, cell, 1)
    res = _run(cell, fault, seconds=LONG[cell], traffic=ONE)
    assert res["attempted"] > 1
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [None, "answer_altered", "short_walk"])
def test_fast_calls_keep_the_tap_bounded(monkeypatch, fault):
    """With the device step jitted the fan-out's calls take milliseconds
    here: the window runs through many schedule blocks, the tap moves its
    calls to the host and checks them whenever it holds ``TAP_BYTES``, and
    a fault planted past the first block still turns ``correct`` false."""
    import jax
    import repro.serving.rag as rag_mod
    cell = "retrieve-fanout-600"
    entry = _entry(cell)
    monkeypatch.setattr(rag_mod, "retrieve_device", jax.jit(
        rag_mod.retrieve_device,
        static_argnames=("max_locs", "n", "lookup_fn", "fused")))
    monkeypatch.setattr(entry, "TAP_BYTES", 1 << 18)
    if fault:
        _break_from_call(monkeypatch, cell, 16)
    res = _run(cell, fault, seconds=2.0, traffic={"schedule_length": 16})
    _, config, traffic, _, _ = harness.resolve(cell)
    bank = config["bank"]
    probe = 4 + 4 + 1 + 4 * bank["max_locs"] * (1 + 2 * bank["hierarchy_n"])
    call = SMALL[cell]["forest"]["num_trees"] * \
        traffic["entities_per_query"] * probe
    assert res["attempted"] > 64
    assert _note(res, r"schedule blocks (\d+)") > 4
    assert _note(res, r"checked in the window (\d+)") > 2
    assert _note(res, r"held at most (\d+)") < entry.TAP_BYTES + call
    assert res["correct"] == (fault is None), res["checks"]
