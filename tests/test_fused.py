"""Fused retrieval kernel: bit-identity against the unfused oracle chain
(``retrieve_device`` -> ``gather_context``) across ragged/skewed forests,
miss-heavy batches, out-of-range tree ids, temperature rounds, and the
tiled-vs-single-block / mxu-vs-direct kernel variants; plus the shared
VMEM-budget derivation and the fused-path observability surface."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                     # pragma: no cover
    from _hypothesis_stub import given, settings, st

from repro.core import (CFTDeviceState, build_bank, build_forest,
                        build_index, hashing, retrieve_device)
from repro.kernels import vmem
from repro.kernels.fused_retrieve import (fused_retrieve_arena,
                                          fused_retrieve_ref,
                                          fused_retrieve_state_auto,
                                          launch_plan)
from repro.obs import get_registry

RNG = np.random.default_rng(7)
FIELDS = ("hit", "locations", "up", "down", "temperature")

_unfused = jax.jit(retrieve_device, static_argnames=("max_locs", "n"))


def _forest(tree_sizes, deep_every=0, seed=0):
    """Ragged forest; every ``deep_every``-th tree gets a skewed
    random-parent tail.  A size-0 entry builds a root-only (empty) tree."""
    rng = np.random.default_rng(seed)
    trees = []
    for t, size in enumerate(tree_sizes):
        names = [f"e{t}_{i}" for i in range(size)]
        edges = [(f"r{t}", n) for n in names]
        if not size:
            edges = [(f"r{t}", f"only{t}")]     # leaf carries the tree
        if deep_every and t % deep_every == 0 and names:
            for j in range(11):
                parent = names[int(rng.integers(len(names)))]
                child = f"e{t}_d{j}"
                edges.append((parent, child))
                names.append(child)
        trees.append(edges)
    return build_forest(trees), trees


def _queries(trees, batch, hit_rate, seed=0, oob=True):
    rng = np.random.default_rng(seed)
    num_trees = len(trees)
    qt = rng.integers(num_trees, size=batch).astype(np.int32)
    qh = np.empty(batch, np.uint32)
    for i in range(batch):
        ents = [c for _, c in trees[qt[i]]]
        if rng.random() < hit_rate and ents:
            qh[i] = hashing.entity_hash(
                ents[int(rng.integers(len(ents)))])
        else:
            qh[i] = rng.integers(1, 2 ** 32)
    if oob and batch >= 4:       # out-of-range ids must miss, not alias
        qt[0], qt[1] = -2, num_trees + 5
    return jnp.asarray(qh), jnp.asarray(qt)


def _assert_same(ref, got, msg=""):
    for f in FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(ref, f)),
                                      np.asarray(getattr(got, f)),
                                      err_msg=f"{f} {msg}")


def _routing(state, qh, qt):
    """The pre-routed arena inputs retrieve_device computes internally."""
    num_trees = state.bucket_offsets.shape[0] - 1
    in_range = (qt >= 0) & (qt < num_trees)
    tq = jnp.where(in_range, qt, 0).astype(jnp.int32)
    row_off = state.bucket_offsets[tq]
    masks = (state.tree_nb[tq] - 1).astype(jnp.uint32)
    return row_off, masks, in_range


# ------------------------------------------------------------ bit identity

@pytest.mark.parametrize("sizes,hit_rate", [
    ((6, 1, 14, 3), 0.9),
    ((2, 9, 0, 5, 7, 4, 11, 3), 0.5),       # includes an empty tree
    (tuple(3 + (t % 6) * 4 for t in range(24)), 0.1),   # miss-heavy
])
def test_fused_matches_unfused(sizes, hit_rate):
    forest, trees = _forest(sizes, deep_every=3)
    state = CFTDeviceState.from_bank(build_bank(forest), forest)
    qh, qt = _queries(trees, 96, hit_rate)
    ref = _unfused(state, qh, qt)
    got = retrieve_device(state, qh, qt, fused=True)
    _assert_same(ref, got)


def test_fused_single_filter_state():
    """from_index states (T == 1, dense arena) take the fused path too."""
    forest, trees = _forest((20, 8, 5))
    idx = build_index(forest, num_buckets=64)
    state = CFTDeviceState.from_index(idx)
    qh, _ = _queries(trees, 40, 0.7, oob=False)
    qt = jnp.zeros((40,), jnp.int32)
    _assert_same(_unfused(state, qh, qt),
                 retrieve_device(state, qh, qt, fused=True))


def test_fused_temperature_rounds():
    """Bump equivalence must hold *cumulatively*: thread each round's
    temperature forward on both paths and compare every round."""
    forest, trees = _forest((8, 12, 4, 9), deep_every=2)
    s_ref = CFTDeviceState.from_bank(build_bank(forest), forest)
    s_fus = CFTDeviceState.from_bank(build_bank(forest), forest)
    for rnd in range(4):
        qh, qt = _queries(trees, 64, 0.8, seed=rnd)
        ref = _unfused(s_ref, qh, qt)
        got = retrieve_device(s_fus, qh, qt, fused=True)
        _assert_same(ref, got, msg=f"round {rnd}")
        s_ref = s_ref.with_temperature(ref.temperature)
        s_fus = s_fus.with_temperature(got.temperature)


def test_fused_lookup_fn_conflict():
    forest, trees = _forest((4,))
    state = CFTDeviceState.from_bank(build_bank(forest), forest)
    qh, qt = _queries(trees, 8, 1.0, oob=False)
    with pytest.raises(ValueError, match="lookup_fn"):
        retrieve_device(state, qh, qt, fused=True,
                        lookup_fn=lambda *a: None)


@settings(max_examples=12, deadline=None)
@given(st.data())
def test_fused_bit_identity_property(data):
    """Hypothesis sweep over forest shape, batch size, hit rate, and
    walk geometry: the fused pass is the unfused chain, bit for bit."""
    num_trees = data.draw(st.integers(min_value=1, max_value=12))
    sizes = tuple(
        data.draw(st.integers(min_value=0, max_value=18))
        for _ in range(num_trees))
    batch = data.draw(st.integers(min_value=1, max_value=150))
    hit_rate = data.draw(st.integers(min_value=0, max_value=10)) / 10.0
    max_locs = data.draw(st.integers(min_value=1, max_value=6))
    n = data.draw(st.integers(min_value=1, max_value=4))
    seed = data.draw(st.integers(min_value=0, max_value=999))
    forest, trees = _forest(sizes, deep_every=2, seed=seed)
    state = CFTDeviceState.from_bank(build_bank(forest), forest)
    qh, qt = _queries(trees, batch, hit_rate, seed=seed)
    ref = _unfused(state, qh, qt, max_locs=max_locs, n=n)
    got = retrieve_device(state, qh, qt, max_locs=max_locs, n=n,
                          fused=True)
    _assert_same(ref, got, msg=f"seed={seed}")


# ------------------------------------------------- kernel variant agreement

def _arena_call(state, qh, qt, **kw):
    row_off, masks, valid = _routing(state, qh, qt)
    return fused_retrieve_arena(
        state.fingerprints, state.temperature, state.heads, row_off,
        masks, valid, qh, state.csr_offsets, state.csr_nodes,
        state.parent, state.entity_id, state.child_offsets,
        state.child_index, **kw)


@pytest.mark.parametrize("mxu", [False, True])
def test_tiled_vs_single_block(mxu):
    """Row-tiled grids (arena split past the VMEM budget) agree exactly
    with the resident single-block launch, in both gather strategies."""
    forest, trees = _forest(tuple(5 for _ in range(40)), deep_every=5)
    state = CFTDeviceState.from_bank(build_bank(forest), forest)
    assert state.fingerprints.shape[0] > 128     # tiling is exercised
    qh, qt = _queries(trees, 70, 0.6)
    ref = _arena_call(state, qh, qt, interpret=True, row_tile=0, mxu=mxu)
    got = _arena_call(state, qh, qt, interpret=True, row_tile=128, mxu=mxu)
    _assert_same(ref, got, msg=f"mxu={mxu}")
    # and both agree with the unfused oracle
    _assert_same(_unfused(state, qh, qt), ref, msg=f"oracle mxu={mxu}")


def test_mxu_matches_direct_gather():
    """The one-hot MXU matmul gathers (TPU strategy) are bit-identical
    to direct clipped indexing — f32-exactness of the dot-gather."""
    forest, trees = _forest((9, 2, 16, 0, 6), deep_every=2)
    state = CFTDeviceState.from_bank(build_bank(forest), forest)
    qh, qt = _queries(trees, 50, 0.5)
    _assert_same(
        _arena_call(state, qh, qt, interpret=True, row_tile=0, mxu=False),
        _arena_call(state, qh, qt, interpret=True, row_tile=0, mxu=True))


def test_ref_matches_oracle():
    """The pure-jnp fused oracle (unrolled walks) is the unfused chain."""
    forest, trees = _forest((7, 3, 12, 5), deep_every=2)
    state = CFTDeviceState.from_bank(build_bank(forest), forest)
    qh, qt = _queries(trees, 33, 0.6)
    row_off, masks, valid = _routing(state, qh, qt)
    got = fused_retrieve_ref(
        state.fingerprints, state.temperature, state.heads, row_off,
        masks, valid, qh, state.csr_offsets, state.csr_nodes,
        state.parent, state.entity_id, state.child_offsets,
        state.child_index)
    _assert_same(_unfused(state, qh, qt), got)


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
    a size, and the fused launch plan runs interpreted, untiled."""
    with pytest.raises(RuntimeError, match="no VMEM"):
        vmem.vmem_capacity_bytes()
    assert launch_plan(4608, 4, 16384, 16384, 10523, 9923) == \
        (True, False, 0, 0)


def test_max_rows_monotone():
    b = vmem.budget_for(16 << 20)
    free = vmem.max_rows_for_vmem(b, 128, 0)
    assert free % 128 == 0 and free >= 128
    # resident context blocks shrink the probe-tile allowance
    assert vmem.max_rows_for_vmem(b, 128, b.budget_bytes // 2) <= free
    # a larger core never holds fewer rows
    assert vmem.max_rows_for_vmem(vmem.budget_for(128 << 20), 128) >= free


# ----------------------------------------------------------- observability

def test_fused_obs_surface():
    reg = get_registry()
    forest, trees = _forest((6, 4))
    state = CFTDeviceState.from_bank(build_bank(forest), forest)
    qh, qt = _queries(trees, 16, 0.9, oob=False)
    before = reg.snapshot()["counters"].get("serve.fused_batches", 0)
    out = fused_retrieve_state_auto(state, qh, qt)
    assert out is not None
    snap = reg.snapshot()
    assert snap["counters"]["serve.fused_batches"] == before + 1
    assert snap["gauges"]["kernel.tile_rows"] == 0      # resident on CPU


def test_session_fused_flip_forgiven():
    """set_fused() is an intentional geometry change: the armed sentinel
    forgives exactly the flip's compile, then trips again."""
    from repro.serving.engine import RetrievalSession
    forest, trees = _forest((8, 5, 3))
    bank = build_bank(forest)
    sess = RetrievalSession()
    sess.attach(CFTDeviceState.from_bank(bank, forest), fused=True)
    qt = [0, 1, 2, 0]
    qh = [int(hashing.entity_hash(c)) for c in
          ("e0_0", "e1_1", "e2_2", "e0_3")]
    a = sess.retrieve(qt, qh)
    sess.sentinel.rebaseline()
    sess.sentinel.arm()
    sess.set_fused(False)
    b = sess.retrieve(qt, qh)
    assert sess.observe() == {}          # flip compile was forgiven
    sess.set_fused(True)
    c = sess.retrieve(qt, qh)
    assert sess.observe() == {}
    np.testing.assert_array_equal(np.asarray(a.hit), np.asarray(b.hit))
    np.testing.assert_array_equal(np.asarray(b.locations),
                                  np.asarray(c.locations))
    sess.sentinel.disarm()


def test_session_fused_matches_unfused():
    from repro.serving.engine import RetrievalSession
    forest, trees = _forest((10, 2, 7, 4), deep_every=2)
    bank = build_bank(forest)
    s_ref = RetrievalSession()
    s_ref.attach(CFTDeviceState.from_bank(bank, forest))
    s_fus = RetrievalSession()
    s_fus.attach(CFTDeviceState.from_bank(bank, forest), fused=True)
    qh, qt = _queries(trees, 48, 0.7)
    for rnd in range(3):
        a = s_ref.retrieve(list(np.asarray(qt)), list(np.asarray(qh)))
        b = s_fus.retrieve(list(np.asarray(qt)), list(np.asarray(qh)))
        _assert_same(a, b, msg=f"round {rnd}")
