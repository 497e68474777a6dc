"""Ragged bucket arena: dense-equivalence property tests (lookup bit-
identity incl. temperature bumps), empty-tree minimum allocation, and
tree-local expansion byte-identity under churn."""
import jax.numpy as jnp
import numpy as np

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                  # offline container
    from _hypothesis_stub import given, settings, st

from repro.core import (CFTDeviceState, MaintenanceEngine,
                        bump_temperature_arena, build_bank, build_forest,
                        lookup_arena, retrieve_device)
from repro.core import hashing
from repro.core.bank import EMPTY_TREE_NB
from repro.kernels.cuckoo_lookup import cuckoo_lookup_arena


def _skewed_forest(rng, num_trees):
    """Random skewed forest: per-tree sizes vary ~25x, empty trees
    allowed, one randomly chosen hot tree blown up further."""
    sizes = rng.integers(0, 14, size=num_trees)
    sizes[int(rng.integers(num_trees))] *= 8
    return build_forest(
        [[(f"r{t}", f"e{t}_{i}") for i in range(int(sizes[t]))]
         for t in range(num_trees)])


def _query_batch(bank, hashes, rng, misses=24):
    tid = np.concatenate([
        bank.row_tree,
        rng.integers(0, bank.num_trees, size=misses)]).astype(np.int32)
    hh = np.concatenate([
        hashes[bank.row_entity] if bank.num_rows else
        np.zeros(0, np.uint32),
        rng.integers(1, 2 ** 32, size=misses).astype(np.uint32)])
    return tid, hh


def _ragged_args(bank, tid):
    """Arena tables plus each query's segment start and bucket mask, as
    ``retrieve_device`` routes in-range tree ids."""
    return (jnp.asarray(bank.fingerprints), jnp.asarray(bank.heads),
            jnp.asarray(bank.bucket_offsets[tid].astype(np.int32)),
            jnp.asarray((bank.tree_nb[tid] - 1).astype(np.uint32)))


# --------------------------------------------------- dense equivalence

@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_ragged_bit_identical_to_dense_equivalent(seed):
    """A forced-uniform build is the dense-equivalent bank: its arena
    reshapes to the old (T, NB, S) layout, and the ragged routed lookup
    must answer bit-identically to the dense reference on every field —
    hit/miss, head, bucket, slot — and produce identical temperature
    bumps."""
    rng = np.random.default_rng(seed)
    forest = _skewed_forest(rng, int(rng.integers(3, 10)))
    bank = build_bank(forest, num_buckets=64)        # uniform forced
    assert bank.num_buckets == 64                    # stayed uniform
    hashes = hashing.hash_entities(forest.entity_names)
    tid, hh = _query_batch(bank, hashes, rng)
    tid_j, hh_j = jnp.asarray(tid), jnp.asarray(hh)

    df, dt, dh = bank.dense_tables()
    t, nb, s = df.shape
    dense_off = tid_j * nb                           # the dense (T, NB) index
    ref = lookup_arena(jnp.asarray(df.reshape(t * nb, s)),
                       jnp.asarray(dh.reshape(t * nb, s)), dense_off,
                       jnp.full(tid.shape, nb - 1, jnp.uint32), hh_j)
    args = _ragged_args(bank, tid)
    got = lookup_arena(*args, hh_j)
    for f in ("hit", "head", "bucket", "slot"):
        np.testing.assert_array_equal(np.asarray(getattr(ref, f)),
                                      np.asarray(getattr(got, f)),
                                      err_msg=f"dense-equivalence {f}")

    # temperature bumps land on the same slots through both layouts
    temp_d = bump_temperature_arena(jnp.asarray(dt.reshape(t * nb, s)),
                                    dense_off, ref)
    temp_r = bump_temperature_arena(jnp.asarray(bank.temperature),
                                    args[2], got)
    np.testing.assert_array_equal(
        np.asarray(temp_d).reshape(np.asarray(temp_r).shape),
        np.asarray(temp_r))


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_ragged_lookup_matches_per_tree_standalone(seed):
    """On a naturally ragged build, routing a query through the arena is
    bit-identical to probing that tree's standalone (nb_t, S) filter
    slice — host path, pure-jnp path and the Pallas kernel agree."""
    rng = np.random.default_rng(seed)
    forest = _skewed_forest(rng, int(rng.integers(3, 10)))
    bank = build_bank(forest)
    hashes = hashing.hash_entities(forest.entity_names)
    tid, hh = _query_batch(bank, hashes, rng)
    args = _ragged_args(bank, tid) + (jnp.asarray(hh),)

    got = lookup_arena(*args)
    ker = cuckoo_lookup_arena(*args, interpret=True)
    m = np.asarray(got.hit)
    np.testing.assert_array_equal(m, np.asarray(ker.hit))
    np.testing.assert_array_equal(np.asarray(got.head),
                                  np.asarray(ker.head))
    for f in ("bucket", "slot"):                     # defined on hits
        np.testing.assert_array_equal(np.asarray(getattr(got, f))[m],
                                      np.asarray(getattr(ker, f))[m])

    for t in range(bank.num_trees):                  # standalone slices
        sel = tid == t
        if not sel.any():
            continue
        lo, hi = bank.segment(t)
        b = int(sel.sum())                           # one-segment arena
        ref = lookup_arena(jnp.asarray(bank.fingerprints[lo:hi]),
                           jnp.asarray(bank.heads[lo:hi]),
                           jnp.zeros((b,), jnp.int32),
                           jnp.full((b,), hi - lo - 1, jnp.uint32),
                           jnp.asarray(hh[sel]))
        for f in ("hit", "head", "bucket", "slot"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ref, f)),
                np.asarray(getattr(got, f))[sel],
                err_msg=f"standalone tree {t} {f}")
    # host reference agrees everywhere
    for i in range(tid.shape[0]):
        hit, row, _ = bank.lookup(int(tid[i]), int(hh[i]))
        assert bool(m[i]) == hit
        if hit:
            assert int(np.asarray(got.head)[i]) == row


# ------------------------------------------------ tree-local expansion

@settings(max_examples=4, deadline=None)
@given(st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_churn_expand_leaves_other_segments_byte_identical(seed):
    """A churn run that overflows one hot tree (queued inserts + a forced
    expand): every other tree's arena segment stays byte-identical across
    all five tables, CSR row ids survive (no renumbering), and every live
    row still answers."""
    rng = np.random.default_rng(seed)
    forest = _skewed_forest(rng, 6)
    bank = build_bank(forest)
    eng = MaintenanceEngine(bank, seed=seed & 0xFFFF)
    hashes = hashing.hash_entities(forest.entity_names)
    hot = int(np.argmax(bank.num_items))
    cold = [t for t in range(bank.num_trees) if t != hot]

    def seg_bytes(t):
        lo, hi = bank.segment(t)
        return tuple(arr[lo:hi].tobytes() for arr in
                     (bank.fingerprints, bank.temperature, bank.heads,
                      bank.entity_ids, bank.stored_hash))

    # churn the hot tree past its load threshold
    cap = int(bank.tree_nb[hot]) * bank.slots
    extra = cap - int(bank.num_items[hot]) + 4
    for i in range(extra):
        eng.queue_insert(hot, f"stuffing {seed}_{i}", [i])
    snaps = {t: seg_bytes(t) for t in cold}
    nb0 = bank.tree_nb.copy()
    rows0 = {r: bank.walk_row(r) for r in range(bank.num_rows)}
    eng.maintain()
    assert eng.stats["expansions"] >= 1
    assert bank.tree_nb[hot] > nb0[hot]
    eng.expand_tree(hot, force=True)                 # and once more
    for t in cold:
        assert bank.tree_nb[t] == nb0[t]
        assert seg_bytes(t) == snaps[t], f"cold segment {t} mutated"
    # CSR rows kept their ids and node lists (tree-local expand never
    # renumbers), and every pre-existing row still resolves
    for r, nodes in rows0.items():
        assert bank.walk_row(r) == nodes
        t, e = int(bank.row_tree[r]), int(bank.row_entity[r])
        hit, row, _ = bank.lookup(t, int(hashes[e]))
        assert hit and row == r
    for i in range(extra):
        h = int(hashing.entity_hash(f"stuffing {seed}_{i}"))
        hit, row, _ = bank.lookup(hot, h)
        assert hit and bank.walk_row(row) == [i]


# ------------------------------------------------- empty-tree allocation

def test_empty_tree_gets_minimum_buckets():
    """Regression: a tree with zero entities used to inherit the shared
    bank-wide NB (the hot tree's bucket count); the ragged builder must
    allocate it the minimum instead."""
    trees = [[("r0", "e0_a"), ("r0", "e0_b")],
             [],                                     # empty tree
             [(f"r2", f"e2_{i}") for i in range(60)]]
    forest = build_forest(trees)
    bank = build_bank(forest)
    assert int(bank.tree_nb[1]) == EMPTY_TREE_NB
    assert int(bank.tree_nb[1]) < int(bank.tree_nb[0]) \
        < int(bank.tree_nb[2])
    assert bank.total_buckets == int(bank.tree_nb.sum())
    # the empty tree answers misses on host + device
    h = int(hashing.entity_hash("e2_0"))
    assert not bank.contains(1, h)
    state = CFTDeviceState.from_bank(bank, forest)
    out = retrieve_device(state, jnp.asarray(np.asarray([h], np.uint32)),
                          jnp.asarray(np.asarray([1], np.int32)))
    assert not bool(out.hit[0])
    # and it can still grow: a late insert expands it tree-locally
    eng = MaintenanceEngine(bank)
    for i in range(9):
        eng.queue_insert(1, f"late {i}", [i])
    eng.maintain()
    assert int(bank.tree_nb[1]) > EMPTY_TREE_NB
    assert int(bank.tree_nb[2]) == 32               # hot tree untouched
    for i in range(9):
        assert bank.locate(1, f"late {i}") == [i]


def test_skewed_forest_arena_bytes_beat_dense():
    """The memory claim at test scale: one 16x tree among 64 — arena rows
    are a small fraction of the dense pad-to-max rows."""
    sizes = [8 * 16 if t == 0 else 8 for t in range(64)]
    forest = build_forest(
        [[(f"r{t}", f"e{t}_{i}") for i in range(sizes[t])]
         for t in range(64)])
    bank = build_bank(forest)
    dense_rows = 64 * int(bank.tree_nb.max())
    assert bank.total_buckets < 0.5 * dense_rows
    # every row still resolves through the packed arena
    hashes = hashing.hash_entities(forest.entity_names)
    rows_i, _ = bank.find_exact(bank.row_tree.astype(np.int64),
                                hashes[bank.row_entity])
    assert (rows_i >= 0).all()
