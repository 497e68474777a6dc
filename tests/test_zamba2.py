"""Zamba2 through the program's normal path against the plain float32
reference (``zamba2_reference.py``), at a small size on the CPU.

The size: ``d_model`` 64, 2 B/C groups of 8 SSM heads, 8 layers with
hybrids at [2, 4, 7], so blocks A, B, A are called and every call's
adapter and linear differ.  Weights are seeded random, every term
exercised: decays and step sizes drawn as Mamba2 initialises them, norm
scales and skips perturbed from one.

Tolerances: the program computes in float32 too, with the SSD scan in
chunks and attention blocked, so the two differ by rounding alone: about
1e-5 of logits of size about 1 here.  ``LOGIT_TOL`` = 2e-4 leaves twenty
times that, and lies four orders of magnitude below what either control
moves (3 to 5 here): the gate after the norm, or the softmax scale of a
224-wide head where the published block scales as for 112.  Both must
fail it.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import zamba2_reference as ref
from repro.configs import get_arch
from repro.models import lm
from repro.serving import ServeEngine

LOGIT_TOL = 2e-4
PROMPT, STEPS = 24, 10


def _cfg():
    return get_arch("zamba2-7b").smoke()


def _weights(cfg, seed=0):
    """Seeded random weights in the program's layout."""
    tree = lm.abstract_params(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    rng = np.random.default_rng(seed)
    out = []
    for path, leaf in leaves:
        name = jax.tree_util.keystr(path)
        shape = leaf.shape
        if "a_log" in name:
            x = np.log(rng.uniform(1, 16, shape))
        elif "dt_bias" in name:
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
            x = dt + np.log(-np.expm1(-dt))          # softplus^-1
        elif "scale" in name or "d_skip" in name:
            x = 1.0 + 0.1 * rng.standard_normal(shape)
        elif "conv_b" in name:
            x = 0.1 * rng.standard_normal(shape)
        elif "conv_w" in name:
            x = 0.5 * rng.standard_normal(shape)
        elif "embed" in name:
            x = rng.standard_normal(shape) * shape[-1] ** -0.5
        else:
            x = rng.standard_normal(shape) * shape[-2] ** -0.5
        out.append(jnp.asarray(x, leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    w = _weights(cfg)
    toks = np.random.default_rng(1).integers(4, cfg.vocab, PROMPT + STEPS)
    return cfg, w, toks.astype(np.int32)


def _ref(cfg, w, toks, variant=""):
    return np.asarray(ref.logits(w, toks, ref.config_of(cfg), variant))


def test_smoke_size_is_the_reference_size():
    cfg = _cfg()
    assert (cfg.d_model, cfg.ssm_groups, cfg.n_layers) == (64, 2, 8)
    assert cfg.hybrid_layers == (2, 4, 7) and cfg.shared_blocks == 2
    assert cfg.head_dim == 2 * cfg.d_model // cfg.n_heads


def test_forward_matches_reference(model):
    cfg, w, toks = model
    prog = np.asarray(lm.forward(cfg, w, {"tokens": jnp.asarray(toks)[None]})
                      )[0, :, :cfg.vocab]
    want = _ref(cfg, w, toks)
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(prog, want, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("variant", ["gate_after_norm", "scale_full"])
def test_controls_fail_the_tolerance(model, variant):
    """The gate applied after the norm, and a softmax scaled for the
    whole 224-wide head, each move the logits far past the tolerance."""
    cfg, w, toks = model
    prog = np.asarray(lm.forward(cfg, w, {"tokens": jnp.asarray(toks)[None]})
                      )[0, :, :cfg.vocab]
    bad = _ref(cfg, w, toks, variant)
    assert np.abs(prog - bad).max() > 10 * LOGIT_TOL


def test_prefill_then_decode_matches_reference(model):
    """Prefill the prompt, then decode ``STEPS - 1`` tokens through the
    KV caches and the conv and SSM state: each step's logits are the
    reference's full forward at that position."""
    cfg, w, toks = model
    want = _ref(cfg, w, toks)
    batch = jnp.asarray(np.stack([toks, toks[::-1]]))
    lg, state = lm.prefill(cfg, w, {"tokens": batch[:, :PROMPT]},
                           cache_size=64)
    got = [np.asarray(lg[0, -1, :cfg.vocab])]
    for i in range(PROMPT, PROMPT + STEPS - 1):
        lg, state = lm.decode_step(cfg, w, batch[:, i:i + 1], state)
        got.append(np.asarray(lg[0, -1, :cfg.vocab]))
    assert len(got) >= 9
    np.testing.assert_allclose(np.stack(got), want[PROMPT - 1:-1],
                               atol=LOGIT_TOL, rtol=0)


def test_generate_serves_the_reference_argmax(model):
    """``ServeEngine.generate``'s greedy tokens: at every served position
    the served token's reference logit is the reference's best, up to
    the tolerance (random weights put near-ties within rounding)."""
    cfg, w, toks = model
    engine = ServeEngine(cfg, w, cache_size=64, batch_size=2)
    prompt = toks[:PROMPT]
    batch = jnp.asarray(np.stack([prompt, prompt[::-1]]))
    out = engine.generate({"tokens": batch}, STEPS)[0]
    seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
    want = _ref(cfg, w, seq)[PROMPT - 1:]
    gap = want.max(-1) - want[np.arange(STEPS), out]
    assert gap.max() <= LOGIT_TOL


def _published_params(layers: int, hybrids: int) -> int:
    """Zamba2-7B's parameters from the catalog's widths: ``layers``
    Mamba2 layers, two shared blocks, ``hybrids`` calls (LoRA and
    linear each) and the tied embedding."""
    d, di, n, g, h, k = 3584, 7168, 64, 2, 112, 4
    ff, r, vocab = 14336, 128, 32000
    conv = di + 2 * g * n
    mamba = (d                                   # input RMSNorm
             + d * (2 * di + 2 * g * n + h)      # in_proj
             + k * conv + conv                   # depthwise conv + bias
             + 3 * h                             # A_log, dt_bias, D
             + di                                # gated norm
             + di * d)                           # out_proj
    block = (2 * d                               # RMSNorm(concat[h, e])
             + 3 * (2 * d) * (2 * d)             # q, k, v: 7168 -> 32 x 224
             + (2 * d) * d                       # o_proj
             + d                                 # pre-MLP RMSNorm
             + d * 2 * ff + ff * d)              # gate_up, down
    call = d * r + r * 2 * ff + d * d            # LoRA + linear
    return layers * mamba + 2 * block + hybrids * call + vocab * d + d


def test_param_count_published():
    cfg = get_arch("zamba2-7b")
    assert lm.param_count(cfg) == _published_params(81, 13)
    assert math.isclose(lm.param_count(cfg), 7.4e9, rel_tol=0.01)


def test_param_count_24_layer_cut():
    cfg = get_arch("zamba2-7b").replace(n_layers=24,
                                        hybrid_layers=(6, 11, 17, 23))
    assert lm.param_count(cfg) == _published_params(24, 4)
    assert math.isclose(lm.param_count(cfg), 2.73e9, rel_tol=0.005)
