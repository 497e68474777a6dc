"""The Zamba2 answer cell's comparison at a small size on the CPU: a clean
run is correct, and a run with a served token or a retrieved answer
changed underneath, or with the float8 control in the program's place,
is not.

The generator is cut to the reference tests' size (``d_model`` 64, two
B/C groups, 8 layers with hybrids at [2, 4, 7]) in float32, where the
program and the reference agree to rounding, so the limit is tight; the
forest to 30 trees.  Run by hand like its siblings:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest \\
        benchmarks/chip/tests/test_zamba2_cell.py
"""
from __future__ import annotations

import numpy as np
import pytest

import harness

CELL = "rag-answer-zamba2-600"
LAYERS = 8
HYBRID = [2, 4, 7]
SMALL = {
    "test_size": True,
    "forest": {"num_trees": 30},
    "hidden_size": 64, "attention_hidden_size": 128,
    "attention_head_dim": 32, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_query_groups": 4, "kv_channels": 16,
    "intermediate_size": 128, "ffn_hidden_size": 128, "mamba_d_state": 16,
    "mamba_headdim": 8, "n_mamba_heads": 16, "adapter_rank": 8,
    "num_hidden_layers": LAYERS, "hybrid_layer_ids": HYBRID,
    "layers_block_type": ["hybrid" if i in HYBRID else "mamba"
                          for i in range(LAYERS)],
    "vocab_size": 512, "torch_dtype": "float32",
    "serving": {"cache_size": 1024, "batch_size": 4},
    "checks": {"logit_gap": 1e-3},
}


def _run(fault=None, trace=False):
    return harness.run_cell(CELL, 2 ** 31 + 77, 4.0, trace,
                            require_tpu=False, config_overrides=SMALL,
                            fault=fault)


def test_clean_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["logit_gap"]["value"] < 1e-4


@pytest.mark.parametrize("fault", ["token_altered", "answer_altered",
                                   "fp8_control"])
def test_broken_path_is_not_correct(fault):
    res = _run(fault)
    assert not res["correct"], res["checks"]


def test_traced_run_reports_its_per_layer_metrics():
    res = _run(trace=True)
    _, _, _, _, layer = harness.resolve(CELL)
    want = {m["name"] for m in layer if m["source"] != "device_trace"}
    assert want <= set(res["metrics"])
    assert 0 < res["metrics"]["answer_mfu.zamba2"]["value"] < 100
    assert res["metrics"]["answer_state_mb.zamba2"]["value"] > 0


def test_flops_closed_form():
    """A token's FLOPs at the cell's widths, from the published sizes."""
    import json
    import os
    import counts_zamba2 as c
    with open(os.path.join(harness.HERE, "configs",
                           "hospital600-zamba2-7b.json")) as f:
        m = json.load(f)
    d, di, ff, r = 3584, 7168, 14336, 128
    mamba = d * (2 * di + 256 + 112) + 4 * (di + 256) + di * d \
        + 2 * 112 * 64 * 64
    call = 3 * 7168 * 7168 + 7168 * d + d * 2 * ff + d * r + r * 2 * ff \
        + ff * d + d * d
    for ctx in (1, 1600):
        want = 2 * (24 * mamba + 4 * call) + 4 * 4 * 7168 * ctx \
            + 2 * d * 32000
        assert c.token_flops(m, ctx, head=True) == want
    assert c.answer_flops(m, 5, 3) == sum(
        c.token_flops(m, i + 1, head=(i == 4)) for i in range(5)) + sum(
        c.token_flops(m, 5 + j + 1, head=True) for j in range(2))
    # 1.88e9 weights and the recurrence: a MAC a weight, 2 * H * N * P more
    assert np.isclose(c.mamba_layer_flops(m) * 24 / 2, 1.90e9, rtol=0.005)
