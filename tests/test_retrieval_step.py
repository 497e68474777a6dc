"""One retrieval step, two probes: ``retrieve_device`` with the Pallas arena
kernel (``cuckoo_lookup_arena_auto``, interpreted off the chip) against the
same step with the pure-jnp reference probe, on all five outputs — across
ragged/skewed forests, miss-heavy batches, out-of-range tree ids,
temperature rounds, the session's padded jitted step and row-tiled
kernel launches."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                     # pragma: no cover
    from _hypothesis_stub import given, settings, st

from repro.core import (CFTDeviceState, build_bank, build_forest,
                        build_index, hashing, lookup_arena, retrieve_device)
from repro.core.trag import NULL
from repro.kernels.cuckoo_lookup.ops import (cuckoo_lookup_arena,
                                             cuckoo_lookup_arena_auto)
from repro.serving.engine import RetrievalSession

FIELDS = ("hit", "locations", "up", "down", "temperature")

_jnp_step = jax.jit(retrieve_device, static_argnames=("max_locs", "n"))


def _kernel_step(state, qh, qt, **kw):
    return retrieve_device(state, qh, qt,
                           lookup_fn=cuckoo_lookup_arena_auto, **kw)


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


def _assert_same(ref, got, msg="", fields=FIELDS):
    for f in fields:
        np.testing.assert_array_equal(np.asarray(getattr(ref, f)),
                                      np.asarray(getattr(got, f)),
                                      err_msg=f"{f} {msg}")


def _bank_state(forest):
    return CFTDeviceState.from_bank(build_bank(forest), forest)


# ---------------------------------------------- kernel step == jnp step

@pytest.mark.parametrize("sizes,hit_rate", [
    ((6, 1, 14, 3), 0.9),
    ((2, 9, 0, 5, 7, 4, 11, 3), 0.5),       # includes an empty tree
    (tuple(3 + (t % 6) * 4 for t in range(24)), 0.1),   # miss-heavy
])
def test_kernel_step_matches_jnp_step(sizes, hit_rate):
    forest, trees = _forest(sizes, deep_every=3)
    state = _bank_state(forest)
    qh, qt = _queries(trees, 96, hit_rate)
    ref = _jnp_step(state, qh, qt)
    _assert_same(ref, _kernel_step(state, qh, qt))
    assert not np.asarray(ref.hit)[:2].any()        # out-of-range ids


def test_kernel_step_single_filter_state():
    """A from_index state is an arena of one segment: T == 1, offsets
    zero, mask nb - 1."""
    forest, trees = _forest((20, 8, 5))
    state = CFTDeviceState.from_index(build_index(forest, num_buckets=64))
    qh, _ = _queries(trees, 40, 0.7, oob=False)
    qt = jnp.zeros((40,), jnp.int32)
    ref = _jnp_step(state, qh, qt)
    assert np.asarray(ref.hit).any()
    _assert_same(ref, _kernel_step(state, qh, qt))


@settings(max_examples=12, deadline=None)
@given(st.data())
def test_kernel_step_property(data):
    """Hypothesis sweep over forest shape, batch size, hit rate and walk
    geometry: the kernel-probed step is the jnp-probed step, bit for
    bit."""
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
    state = _bank_state(forest)
    qh, qt = _queries(trees, batch, hit_rate, seed=seed)
    _assert_same(_jnp_step(state, qh, qt, max_locs=max_locs, n=n),
                 _kernel_step(state, qh, qt, max_locs=max_locs, n=n),
                 msg=f"seed={seed}")


@pytest.mark.parametrize("row_tile", [128, 384])
def test_probe_tiled_matches_single_block(row_tile):
    """Row-tiled kernel grids (a query's two candidate rows may fall in
    different tiles) agree exactly with the single-block launch and the
    jnp probe on a skewed ragged arena, hits and misses alike."""
    forest, trees = _forest(tuple(2 + (t % 8) ** 2 for t in range(96)),
                            deep_every=5)
    state = _bank_state(forest)
    assert state.fingerprints.shape[0] > 2 * row_tile    # several tiles
    assert len(set(np.asarray(state.tree_nb).tolist())) > 2
    qh, qt = _queries(trees, 70, 0.6, oob=False)
    off = state.bucket_offsets[qt]
    masks = (state.tree_nb[qt] - 1).astype(jnp.uint32)
    args = (state.fingerprints, state.heads, off, masks, qh)
    base = cuckoo_lookup_arena(*args, interpret=True, row_tile=0)
    got = cuckoo_lookup_arena(*args, interpret=True, row_tile=row_tile)
    fields = ("hit", "head", "bucket", "slot")
    _assert_same(base, got, msg=f"row_tile={row_tile}", fields=fields)
    ref = lookup_arena(*args)
    m = np.asarray(ref.hit)
    assert m.any() and not m.all()
    _assert_same(ref, got, fields=("hit", "head"))
    for f in ("bucket", "slot"):                # defined only on hits
        np.testing.assert_array_equal(np.asarray(getattr(ref, f))[m],
                                      np.asarray(getattr(got, f))[m])


# ----------------------------------------------------- session step

def _session(state):
    sess = RetrievalSession()
    sess.attach(state, lookup_fn=cuckoo_lookup_arena_auto)
    return sess


def test_session_temperature_rounds():
    """Bump equivalence holds cumulatively: the session threads each
    round's temperature into its state, the eager jnp step threads it by
    hand, and every round agrees on all five outputs."""
    forest, trees = _forest((8, 12, 4, 9), deep_every=2)
    s_ref = _bank_state(forest)
    sess = _session(_bank_state(forest))
    for rnd in range(4):
        qh, qt = _queries(trees, 64, 0.8, seed=rnd)
        ref = _jnp_step(s_ref, qh, qt)
        got = sess.retrieve(list(np.asarray(qt)), list(np.asarray(qh)))
        _assert_same(ref, got, msg=f"round {rnd}")
        np.testing.assert_array_equal(np.asarray(sess.state.temperature),
                                      np.asarray(ref.temperature))
        s_ref = s_ref.with_temperature(ref.temperature)
    assert int(np.asarray(s_ref.temperature).sum()) > 0


def test_session_padded_step_matches_eager():
    """The session pads a batch to its shape-stable geometry and runs the
    jitted step; sliced back, it answers as the eager step on the exact
    batch."""
    forest, trees = _forest((10, 2, 7, 4), deep_every=2)
    state = _bank_state(forest)
    qh, qt = _queries(trees, 45, 0.7)
    sess = _session(state)
    assert sess.batch_pad > 45                      # really padded
    got = sess.retrieve(list(np.asarray(qt)), list(np.asarray(qh)))
    ref = _kernel_step(state, qh, qt)
    _assert_same(ref, got, fields=("hit", "locations", "up", "down"))
    # pad slots (tree 0, hash 0) hold no entry here, so the bumps agree
    pad = lookup_arena(state.fingerprints, state.heads,
                       jnp.zeros((1,), jnp.int32),
                       (state.tree_nb[:1] - 1).astype(jnp.uint32),
                       jnp.zeros((1,), jnp.uint32))
    assert not bool(pad.hit[0])
    _assert_same(ref, got, fields=("temperature",))


def test_out_of_range_trees_miss_in_session_step():
    """Negative and too-large tree ids miss — no clamping to the last
    tree, no aliasing to tree 0 — and bump nothing."""
    forest, trees = _forest((6, 5, 7))
    state = _bank_state(forest)
    t = state.num_trees
    names = [c for _, c in trees[t - 1]] + [c for _, c in trees[0]]
    hh = [int(hashing.entity_hash(c)) for c in names]
    ids = [t, t + 100, -1, -7]
    sess = _session(state)
    got = sess.retrieve([ids[i % 4] for i in range(len(hh))], hh)
    assert not np.asarray(got.hit).any()
    assert (np.asarray(got.locations) == NULL).all()
    assert (np.asarray(got.up) == NULL).all()
    np.testing.assert_array_equal(np.asarray(got.temperature),
                                  np.asarray(state.temperature))
    # the same entities in their own trees all hit
    own = [t - 1] * (len(trees[t - 1])) + [0] * len(trees[0])
    assert np.asarray(sess.retrieve(own, hh).hit).all()
