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


def _run(cell, fault=None, trace=False):
    return harness.run_cell(cell, 2 ** 31 + 77, SECONDS.get(cell, 1.0),
                            trace, require_tpu=False,
                            config_overrides=SMALL[cell], fault=fault)


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


def test_window_loads_nothing_from_the_compile_cache():
    """``RAGPipeline.retrieve`` compiles its programs afresh in every call.
    Even where set-up wrote them to the persistent cache (a floor of 0
    here; under JAX's 1 s floor, a compile that happened to be slow), the
    window compiles them again, as a deployment whose cache lacks them."""
    import jax
    from jax._src import compilation_cache
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    try:
        res = _run("retrieve-fanout-600")
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
    assert res["correct"] and res["attempted"] > 0
    notes = " ".join(res["notes"])
    assert "window compiles 0" not in notes
    assert "persistent-cache loads 0" in notes
