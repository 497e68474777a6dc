"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_bank, build_forest, build_index, lookup_arena
from repro.core import hashing
from repro.kernels import vmem
from repro.kernels.cuckoo_lookup import cuckoo_lookup_arena
from repro.kernels.cuckoo_lookup.ops import launch_vmem_limit, pick_row_tile
from repro.kernels.decode_attention import (combine_partial_attention,
                                            decode_attention,
                                            decode_attention_ref)
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.linear_scan import linear_scan, linear_scan_ref
from repro.kernels.linear_scan.ref import linear_scan_chunked

RNG = np.random.default_rng(42)


# ------------------------------------------------------------ cuckoo lookup

def _one_segment(forest, num_buckets, names):
    """The single filter as an arena of one segment: zero offsets, masks
    ``nb - 1``."""
    t = build_index(forest, num_buckets=num_buckets).filter.tables()
    b = len(names)
    return (jnp.asarray(t.fingerprints), jnp.asarray(t.heads),
            jnp.zeros((b,), jnp.int32),
            jnp.full((b,), num_buckets - 1, jnp.uint32))


def _ragged(forest, _num_buckets, names):
    """The same rows as a ragged bank, each query routed to the tree that
    holds its entity (misses to tree 0)."""
    bank = build_bank(forest)
    tree = np.zeros(len(names), np.int32)
    for i, nm in enumerate(names):
        eid = forest.name_to_id.get(nm)
        if eid is not None:
            tree[i] = forest.entity_locations[eid][0][0]
    return (jnp.asarray(bank.fingerprints), jnp.asarray(bank.heads),
            jnp.asarray(bank.bucket_offsets[tree].astype(np.int32)),
            jnp.asarray((bank.tree_nb[tree] - 1).astype(np.uint32)))


@pytest.mark.parametrize("layout", [_one_segment, _ragged],
                         ids=["one_segment", "ragged"])
@pytest.mark.parametrize("num_buckets,n_entities,batch",
                         [(64, 100, 16), (256, 500, 130), (1024, 3000, 256),
                          (2048, 5000, 97)])
def test_cuckoo_lookup_sweep(num_buckets, n_entities, batch, layout):
    trees = [[(f"r{t}", f"e{t}_{i}") for i in range(n_entities // 40)]
             for t in range(40)]
    forest = build_forest(trees)
    names = ([forest.entity_names[i % forest.num_entities]
              for i in range(batch - 10)] + [f"miss{i}" for i in range(10)])
    h = jnp.asarray(hashing.hash_entities(names))
    fps, heads, off, masks = layout(forest, num_buckets, names)
    ref = lookup_arena(fps, heads, off, masks, h)
    ker = cuckoo_lookup_arena(fps, heads, off, masks, h, interpret=True)
    m = np.asarray(ref.hit)
    assert m[:batch - 10].all() and not m[batch - 10:].any()
    np.testing.assert_array_equal(m, np.asarray(ker.hit))
    np.testing.assert_array_equal(np.asarray(ref.head), np.asarray(ker.head))
    np.testing.assert_array_equal(np.asarray(ref.bucket)[m],
                                  np.asarray(ker.bucket)[m])
    np.testing.assert_array_equal(np.asarray(ref.slot)[m],
                                  np.asarray(ker.slot)[m])


# --------------------------------------------------------- VMEM derivation

def test_vmem_budget_derivation():
    """The tile budget of a core is half its VMEM at the closed-form
    per-row cost, launched under a scoped limit of that capacity."""
    for capacity in (16 << 20, 128 << 20):
        b = vmem.budget_for(capacity)
        assert b.budget_bytes == capacity * vmem.BUDGET_FRACTION
        assert b.per_row_bytes == vmem.closed_form_row_bytes(4, 128) > 0
        assert b.limit_bytes == capacity


def test_vmem_budget_measured_on_cpu():
    """The CPU has no VMEM: the capacity lookup raises rather than assume
    a size, and the probe launches interpreted, untiled, under the
    compiler's default limit."""
    with pytest.raises(RuntimeError, match="no VMEM"):
        vmem.vmem_capacity_bytes()
    assert pick_row_tile(4608, interpret=True) == 0
    assert launch_vmem_limit(interpret=True) == 0


def test_max_rows_monotone():
    b = vmem.budget_for(16 << 20)
    free = vmem.max_rows_for_vmem(b, 128)
    assert free % 128 == 0 and free >= 128
    # a larger core never holds fewer rows
    assert vmem.max_rows_for_vmem(vmem.budget_for(128 << 20), 128) >= free


# ---------------------------------------------------------- flash attention

@pytest.mark.parametrize("b,hq,hkv,lq,lkv,d", [
    (1, 4, 4, 128, 128, 64),      # MHA, tile-aligned
    (2, 8, 2, 256, 256, 64),      # GQA 4:1
    (1, 6, 2, 200, 200, 32),      # unaligned length
    (2, 4, 1, 384, 384, 128),     # MQA, head_dim 128
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, hq, hkv, lq, lkv, d, dtype):
    q = jnp.asarray(RNG.normal(size=(b, hq, lq, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, hkv, lkv, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, hkv, lkv, d)), dtype)
    out = flash_attention(q, k, v, True, None, True)
    ref = attention_ref(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_grads():
    b, hq, hkv, l, d = 2, 4, 2, 256, 64
    q = jnp.asarray(RNG.normal(size=(b, hq, l, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, hkv, l, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, hkv, l, d)), jnp.float32)
    gk = jax.grad(lambda *a: jnp.sum(jnp.sin(
        flash_attention(*a, True, None, True))), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(jnp.sin(
        attention_ref(*a, causal=True))), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(a, b_, atol=5e-5, rtol=5e-5)


# --------------------------------------------------------- decode attention

@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (2, 8, 2, 549, 64), (1, 14, 2, 1024, 64), (4, 4, 4, 300, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, hq, hkv, s, d, dtype):
    q = jnp.asarray(RNG.normal(size=(b, hq, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), dtype)
    lens = jnp.asarray(RNG.integers(1, s + 1, size=(b,)), jnp.int32)
    out = decode_attention(q, k, v, lens, interpret=True)
    ref = decode_attention_ref(q, k, v, lens)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_decoding_combine():
    """Sequence-sharded partial attention == monolithic (long_500k path)."""
    b, hq, hkv, s, d = 2, 8, 2, 768, 64
    q = jnp.asarray(RNG.normal(size=(b, hq, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), jnp.float32)
    lens = jnp.asarray([s, 500], jnp.int32)
    ref = decode_attention_ref(q, k, v, lens)
    shards = 3
    outs, lses = [], []
    for i in range(shards):
        lo, hi = i * s // shards, (i + 1) * s // shards
        local = jnp.clip(lens - lo, 0, hi - lo)
        o, l = decode_attention(q, k[:, :, lo:hi], v[:, :, lo:hi], local,
                                interpret=True, return_lse=True)
        outs.append(o)
        lses.append(l)
    combined = combine_partial_attention(jnp.stack(outs), jnp.stack(lses))
    np.testing.assert_allclose(combined, ref, atol=3e-5, rtol=3e-5)


# -------------------------------------------------------------- linear scan

@pytest.mark.parametrize("b,h,l,dk,dv", [
    (1, 2, 64, 16, 16), (2, 3, 273, 32, 48), (1, 4, 512, 64, 64),
])
@pytest.mark.parametrize("inclusive", [True, False])
@pytest.mark.parametrize("decay_scale", [0.05, 1.0, 8.0])
def test_linear_scan_sweep(b, h, l, dk, dv, inclusive, decay_scale):
    q = jnp.asarray(RNG.normal(size=(b, h, l, dk)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, h, l, dk)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, h, l, dv)), jnp.float32)
    g = jnp.asarray(-np.abs(RNG.normal(size=(b, h, l, dk))) * decay_scale,
                    jnp.float32)
    s0 = jnp.asarray(RNG.normal(size=(b, h, dk, dv)), jnp.float32)
    out_k, s_k = linear_scan(q, k, v, g, s0, inclusive=inclusive,
                             interpret=True)
    out_r, s_r = linear_scan_ref(q, k, v, g, s0, inclusive=inclusive)
    np.testing.assert_allclose(out_k, out_r, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(s_k, s_r, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("inclusive", [True, False])
@pytest.mark.parametrize("decay_scale", [0.05, 8.0])
def test_linear_scan_chunked_per_head_decay(inclusive, decay_scale):
    """One decay per head, (B, H, L, 1) as Mamba2's: the chunked form's
    matmul path equals the sequential reference with the decay broadcast
    over the key width."""
    b, h, l, dk, dv = 2, 3, 150, 16, 8
    q = jnp.asarray(RNG.normal(size=(b, h, l, dk)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, h, l, dk)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, h, l, dv)), jnp.float32)
    g = jnp.asarray(-np.abs(RNG.normal(size=(b, h, l, 1))) * decay_scale,
                    jnp.float32)
    s0 = jnp.asarray(RNG.normal(size=(b, h, dk, dv)), jnp.float32)
    out_c, s_c = linear_scan_chunked(q, k, v, g, s0, inclusive=inclusive,
                                     chunk=64)
    out_r, s_r = linear_scan_ref(q, k, v, jnp.broadcast_to(g, q.shape), s0,
                                 inclusive=inclusive)
    np.testing.assert_allclose(out_c, out_r, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(s_c, s_r, atol=2e-4, rtol=2e-4)


def test_linear_scan_bf16():
    b, h, l, dk, dv = 1, 2, 128, 32, 32
    q = jnp.asarray(RNG.normal(size=(b, h, l, dk)), jnp.bfloat16)
    k = jnp.asarray(RNG.normal(size=(b, h, l, dk)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(b, h, l, dv)), jnp.bfloat16)
    g = jnp.asarray(-np.abs(RNG.normal(size=(b, h, l, dk))) * 0.1,
                    jnp.float32)
    out_k, s_k = linear_scan(q, k, v, g, None, interpret=True)
    out_r, s_r = linear_scan_ref(q, k, v, g, None)
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               atol=5e-2, rtol=5e-2)
