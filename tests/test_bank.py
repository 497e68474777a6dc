"""FilterBank: bulk build, per-tree routing, jnp + Pallas arena lookups."""
import jax.numpy as jnp
import numpy as np

from repro.core import (CFTDeviceState, build_bank, build_forest,
                        lookup_arena, retrieve_device)
from repro.core import hashing
from repro.data import hospital_corpus
from repro.kernels.cuckoo_lookup import cuckoo_lookup_arena
from repro.kernels.cuckoo_lookup.ops import padded_rows


def _forest(num_trees=16, shared=True):
    trees = [[(f"root {t}", f"entity {t}_{i}") for i in range(12)]
             for t in range(num_trees)]
    if shared:
        for t in range(num_trees):          # one entity spanning all trees
            trees[t].append((f"root {t}", "shared entity"))
    return build_forest(trees)


def _routed(bank, tid):
    """Per-query segment start and bucket mask of in-range tree ids, as
    ``retrieve_device`` gathers them."""
    tid = np.asarray(tid)
    return (jnp.asarray(bank.bucket_offsets[tid].astype(np.int32)),
            jnp.asarray((bank.tree_nb[tid] - 1).astype(np.uint32)))


def _dense_arena(bank, tid):
    """A uniform bank's dense ``(T, NB, S)`` view as an arena of T equal
    segments, queries routed to ``tree * NB``."""
    df, _, dh = bank.dense_tables()
    t, nb, s = df.shape
    tid = jnp.asarray(tid, jnp.int32)
    return (jnp.asarray(df.reshape(t * nb, s)),
            jnp.asarray(dh.reshape(t * nb, s)),
            tid * nb, jnp.full(tid.shape, nb - 1, jnp.uint32))


def test_round_trip_every_row():
    """Every inserted (tree, entity) resolves in its own tree with its own
    CSR row and entity-id payload."""
    forest = _forest()
    bank = build_bank(forest)
    hashes = hashing.hash_entities(forest.entity_names)
    for r in range(bank.num_rows):
        t, e = int(bank.row_tree[r]), int(bank.row_entity[r])
        hit, row, eid = bank.lookup(t, int(hashes[e]))
        assert hit and row == r and eid == e
        nodes = bank.walk_row(r)
        assert nodes and all(int(forest.tree_id[nd]) == t for nd in nodes)
        assert all(int(forest.entity_id[nd]) == e for nd in nodes)


def test_no_cross_tree_leakage():
    """Probing a tree that doesn't hold the entity must (almost) always
    miss — residual hits are fingerprint collisions at the filter's
    documented ~0.1% rate — and even a collision can only return rows of
    the probed tree, so foreign locations never leak."""
    forest = _forest(num_trees=8, shared=False)
    bank = build_bank(forest)
    hashes = hashing.hash_entities(forest.entity_names)
    cross = probes = 0
    for r in range(bank.num_rows):
        home = int(bank.row_tree[r])
        h = int(hashes[int(bank.row_entity[r])])
        for t in range(bank.num_trees):
            if t == home:
                continue
            probes += 1
            hit, row, _ = bank.lookup(t, h)
            if hit:
                cross += 1
                assert int(bank.row_tree[row]) == t   # only local rows
                assert all(int(forest.tree_id[nd]) == t
                           for nd in bank.walk_row(row))
    assert cross / probes < 0.01


def test_bulk_build_equals_sequential_insert():
    """The vectorized bulk path and the per-item scalar path must agree on
    membership, payloads, and per-tree item counts."""
    corpus = hospital_corpus(num_trees=30)
    forest = build_forest(corpus.trees)
    bulk = build_bank(forest, bulk=True)
    seq = build_bank(forest, bulk=False)
    assert np.array_equal(bulk.tree_nb, seq.tree_nb)
    assert np.array_equal(bulk.bucket_offsets, seq.bucket_offsets)
    assert np.array_equal(bulk.num_items, seq.num_items)
    assert bulk.build_stats["evicted"] <= bulk.build_stats["items"] // 10
    hashes = hashing.hash_entities(forest.entity_names)
    for r in range(bulk.num_rows):
        t = int(bulk.row_tree[r])
        h = int(hashes[int(bulk.row_entity[r])])
        assert bulk.lookup(t, h) == seq.lookup(t, h)
    occ_b = np.add.reduceat((bulk.fingerprints
                             != hashing.EMPTY_FP).sum(axis=1),
                            bulk.bucket_offsets[:-1])
    occ_s = np.add.reduceat((seq.fingerprints
                             != hashing.EMPTY_FP).sum(axis=1),
                            seq.bucket_offsets[:-1])
    assert np.array_equal(occ_b, occ_s)


def test_routed_lookup_matches_host():
    forest = _forest()
    bank = build_bank(forest)
    hashes = hashing.hash_entities(forest.entity_names)
    tid = np.concatenate([bank.row_tree,
                          np.zeros(16, np.int32)]).astype(np.int32)
    hh = np.concatenate([hashes[bank.row_entity],
                         hashing.hash_entities([f"missing {i}"
                                                for i in range(16)])])
    res = lookup_arena(jnp.asarray(bank.fingerprints),
                       jnp.asarray(bank.heads), *_routed(bank, tid),
                       jnp.asarray(hh))
    for i in range(tid.shape[0]):
        hit, row, _ = bank.lookup(int(tid[i]), int(hh[i]))
        assert bool(res.hit[i]) == hit
        if hit:
            assert int(res.head[i]) == row


def test_vmapped_lookup_matches_per_tree_reference():
    """Each tree's dense filter probed alone, as an arena of one segment,
    answers as the whole bank probed with per-tree routing — through the
    jnp probe and the kernel."""
    forest = _forest()
    bank = build_bank(forest)
    names = [[f"entity {t}_{i}" for i in range(12)] + ["missing x", "shared entity"]
             for t in range(bank.num_trees)]
    hb = np.stack([hashing.hash_entities(ns) for ns in names])
    t_n, b = hb.shape
    tid = np.repeat(np.arange(t_n, dtype=np.int32), b)
    args = _dense_arena(bank, tid) + (jnp.asarray(hb.reshape(-1)),)
    got = lookup_arena(*args)
    ker = cuckoo_lookup_arena(*args, interpret=True)
    df, _, dh = bank.dense_tables()         # uniform forest -> dense view
    nb = df.shape[1]
    for t in range(bank.num_trees):
        ref = lookup_arena(jnp.asarray(df[t]), jnp.asarray(dh[t]),
                           jnp.zeros((b,), jnp.int32),
                           jnp.full((b,), nb - 1, jnp.uint32),
                           jnp.asarray(hb[t]))
        m = np.asarray(ref.hit)
        rows = slice(t * b, (t + 1) * b)
        for field in ("hit", "head"):
            want = np.asarray(getattr(ref, field))
            np.testing.assert_array_equal(
                np.asarray(getattr(got, field))[rows], want)
            np.testing.assert_array_equal(
                np.asarray(getattr(ker, field))[rows], want)
        for field in ("bucket", "slot"):      # defined only on hits
            np.testing.assert_array_equal(
                np.asarray(getattr(got, field))[rows][m],
                np.asarray(getattr(ref, field))[m])
            np.testing.assert_array_equal(
                np.asarray(getattr(ker, field))[rows][m],
                np.asarray(getattr(ref, field))[m])


def test_pallas_bank_kernel_matches_reference():
    forest = _forest()
    bank = build_bank(forest)
    hashes = hashing.hash_entities(forest.entity_names)
    tid = bank.row_tree.astype(np.int32)
    args = _dense_arena(bank, tid) + (jnp.asarray(hashes[bank.row_entity]),)
    ref = lookup_arena(*args)
    ker = cuckoo_lookup_arena(*args, interpret=True)
    for field in ("hit", "head", "bucket", "slot"):
        np.testing.assert_array_equal(np.asarray(getattr(ref, field)),
                                      np.asarray(getattr(ker, field)))


def test_pallas_bank_kernel_tree_tiled_matches_single_block():
    """A grid tiled by whole trees' worth of arena rows must be
    bit-identical to the single-VMEM-block kernel on every lane (hits AND
    misses), for tile sizes that do and do not divide T."""
    forest = _forest(num_trees=12)
    bank = build_bank(forest)
    hashes = hashing.hash_entities(forest.entity_names)
    tid = np.concatenate([bank.row_tree,
                          np.full(24, 5, np.int32)]).astype(np.int32)
    hh = np.concatenate([hashes[bank.row_entity],
                         hashing.hash_entities([f"missing {i}"
                                                for i in range(24)])])
    args = _dense_arena(bank, tid) + (jnp.asarray(hh),)
    nb = bank.dense_tables()[0].shape[1]
    ref = lookup_arena(*args)
    m = np.asarray(ref.hit)
    base = cuckoo_lookup_arena(*args, interpret=True, row_tile=0)
    for tt in (1, 4, 5, 12, -1):   # 5 does not divide T=12 -> pad path
        rt = -1 if tt < 0 else padded_rows(tt * nb, 0)
        ker = cuckoo_lookup_arena(*args, interpret=True, row_tile=rt)
        for field in ("hit", "head", "bucket", "slot"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ker, field)),
                np.asarray(getattr(base, field)),
                err_msg=f"tree_tile={tt} {field}")
        np.testing.assert_array_equal(np.asarray(ker.hit), m)
        np.testing.assert_array_equal(np.asarray(ker.head),
                                      np.asarray(ref.head))
        for field in ("bucket", "slot"):       # defined only on hits
            np.testing.assert_array_equal(
                np.asarray(getattr(ker, field))[m],
                np.asarray(getattr(ref, field))[m])


def test_bank_auto_tiling_threshold():
    """Auto selection keeps small banks single-block and tiles big ones,
    on a core of either VMEM size; interpret mode never tiles."""
    from repro.kernels import vmem
    from repro.kernels.cuckoo_lookup.ops import pick_row_tile
    for capacity in (16 << 20, 128 << 20):
        cap = vmem.max_rows_for_vmem(vmem.budget_for(capacity), 128)
        assert cap % 128 == 0 and cap >= 128
        assert vmem.row_tile_for(4 * 64, cap) == 0
        assert vmem.row_tile_for(cap, cap) == 0
        assert vmem.row_tile_for(2 * cap, cap) == cap
    assert pick_row_tile(1 << 20, interpret=True) == 0


def test_absorb_temperature_replaces_handrolled_writeback():
    forest = _forest(num_trees=4)
    bank = build_bank(forest)
    state = CFTDeviceState.from_bank(bank, forest)
    hashes = hashing.hash_entities(forest.entity_names)
    tid = jnp.asarray(bank.row_tree[:8].astype(np.int32))
    hh = jnp.asarray(hashes[bank.row_entity[:8]])
    out = retrieve_device(state, hh, query_trees=tid)
    bumps = bank.absorb_temperature(state.with_temperature(out.temperature))
    assert bumps == 8
    np.testing.assert_array_equal(bank.temperature,
                                  np.asarray(out.temperature))
    # shape mismatch (stale layout after an expand) must be loud
    try:
        bank.absorb_temperature(np.zeros((1, 2, 3), np.int32))
        assert False, "expected ValueError"
    except ValueError:
        pass


def test_retrieve_device_routes_to_queried_tree():
    forest = _forest()
    bank = build_bank(forest)
    state = CFTDeviceState.from_bank(bank, forest)
    hashes = hashing.hash_entities(forest.entity_names)
    tid = jnp.asarray(bank.row_tree.astype(np.int32))
    hh = jnp.asarray(hashes[bank.row_entity])
    out = retrieve_device(state, hh, query_trees=tid, max_locs=4, n=3)
    assert bool(out.hit.all())
    for r in range(bank.num_rows):
        got = [int(v) for v in np.asarray(out.locations[r]) if v >= 0]
        want = bank.walk_row(r)[:4]
        assert got == want
        # every location stays inside the queried tree
        assert all(int(forest.tree_id[nd]) == int(bank.row_tree[r])
                   for nd in got)


def test_shared_entity_isolated_per_tree():
    """An entity present in every tree yields only the queried tree's
    nodes — the cross-tree locations stay invisible to a routed query."""
    forest = _forest(num_trees=6, shared=True)
    bank = build_bank(forest)
    h = int(hashing.entity_hash("shared entity"))
    eid = forest.name_to_id["shared entity"]
    all_nodes = {t: [nd for tt, nd in forest.entity_locations[eid]
                     if tt == t] for t in range(6)}
    for t in range(6):
        hit, row, got_eid = bank.lookup(t, h)
        assert hit and got_eid == eid
        assert bank.walk_row(row) == all_nodes[t]
