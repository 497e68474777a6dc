"""Distribution tests — run in SUBPROCESSES with XLA host-device counts so
the main pytest process keeps its single default device (dry-run rule:
never set the flag globally)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

# every test here spawns a fresh interpreter + an 8-device host mesh —
# the expensive tier CI runs as its own job (see .github/workflows/ci.yml)
pytestmark = pytest.mark.slow

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"       # host devices only, never the chip
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=520)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_sharded_filter_lookup():
    """Legacy bucket-striped single filter — now a wrapper over the
    bank-axis all-to-all router; bit-identical to lookup_batch."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import build_forest, build_index, lookup_batch
    from repro.core import hashing
    from repro.core.distributed import shard_filter_tables, sharded_lookup
    from repro.data import hospital_corpus

    c = hospital_corpus(num_trees=15)
    forest = build_forest(c.trees)
    idx = build_index(forest, num_buckets=256)
    t = idx.filter.tables()
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    fps, heads = shard_filter_tables(mesh, "model",
                                     jnp.asarray(t.fingerprints),
                                     jnp.asarray(t.heads))
    names = forest.entity_names[:64] + ["missing A", "missing B"]
    h = jnp.asarray(hashing.hash_entities(names))
    ref = lookup_batch(jnp.asarray(t.fingerprints), jnp.asarray(t.heads), h)
    got = sharded_lookup(mesh, "model", fps, heads, h)
    for f in ("hit", "head", "bucket", "slot"):
        np.testing.assert_array_equal(np.asarray(getattr(ref, f)),
                                      np.asarray(getattr(got, f)),
                                      err_msg=f)
    print("sharded lookup OK")
    """)


def test_bank_axis_sharded_lookup_equivalence():
    """Bank-axis sharding: all-to-all routed lookup is bit-identical to
    lookup_batch_ragged on the merged replicated arena — queries hitting
    trees on every shard, a ragged batch size, and an all-miss batch."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import (build_forest, build_bank, lookup_batch_ragged,
                            sharded_lookup_bank, stage_sharded_bank)
    from repro.core import hashing

    T, D = 32, 8
    trees = [[(f"r{t}", f"e{t}_{i}") for i in range(4 + (t % 5) * 3)]
             for t in range(T)]
    forest = build_forest(trees)
    bank = build_bank(forest)
    sbank = bank.shard(D)
    mesh = jax.make_mesh((D,), ("model",))
    state = stage_sharded_bank(sbank, forest, mesh, "model")
    mf, _, mh = sbank.merged_tables()
    moff, mnb = sbank.merged_layout()
    moff_j = jnp.asarray(moff.astype(np.int32))
    mnb_j = jnp.asarray(mnb)

    def check(qt, qh):
        ref = lookup_batch_ragged(jnp.asarray(mf), jnp.asarray(mh),
                                  moff_j, mnb_j,
                                  jnp.asarray(qt), jnp.asarray(qh))
        got = sharded_lookup_bank(state, jnp.asarray(qt), jnp.asarray(qh))
        for f in ("hit", "head", "bucket", "slot"):
            np.testing.assert_array_equal(np.asarray(getattr(ref, f)),
                                          np.asarray(getattr(got, f)),
                                          err_msg=f)
        return ref, got

    # hits on every shard + interleaved misses; B=113 not divisible by D
    rng = np.random.default_rng(0)
    qt = [t for t in range(T) for _ in range(3)] + \
         [int(rng.integers(T)) for _ in range(17)]
    qh = [int(hashing.entity_hash(f"e{t}_{k}"))
          for t in range(T) for k in (0, 1, 2)] + \
         [int(rng.integers(1, 2 ** 32)) for _ in range(17)]
    qt, qh = np.asarray(qt, np.int32), np.asarray(qh, np.uint32)
    ref, got = check(qt, qh)
    hit = np.asarray(got.hit)
    assert hit[:3 * T].all(), "every stored entity must hit"
    owners = sbank.tree_shard_map()[qt[hit]]
    assert set(owners.tolist()) == set(range(D)), "hits on every shard"

    # semantic equivalence vs the original unsharded bank: same hits,
    # identical node lists through the merged row numbering
    ref0 = lookup_batch_ragged(
        jnp.asarray(bank.fingerprints), jnp.asarray(bank.heads),
        jnp.asarray(bank.bucket_offsets.astype(np.int32)),
        jnp.asarray(bank.tree_nb), jnp.asarray(qt), jnp.asarray(qh))
    np.testing.assert_array_equal(np.asarray(ref0.hit), hit)
    gh, rh = np.asarray(got.head), np.asarray(ref0.head)
    for j in np.flatnonzero(hit):
        assert sbank.walk_row(int(gh[j])) == bank.walk_row(int(rh[j]))

    # all-miss batch
    qt_m = np.arange(24, dtype=np.int32) % T
    qh_m = np.asarray([int(hashing.entity_hash(f"missing {j}"))
                       for j in range(24)], np.uint32)
    _, got_m = check(qt_m, qh_m)
    assert not np.asarray(got_m.hit).any()

    # the row-tiled Pallas arena kernel as the shard-local probe;
    # bucket/slot compare on hits only — on a miss the kernel reports the
    # last probed position, the jnp reference reports (i1, 0) (both are
    # dont-cares: head is NULL and the hit-masked temperature add is 0)
    from repro.kernels.cuckoo_lookup.ops import cuckoo_lookup_arena_auto
    got_k = sharded_lookup_bank(state, jnp.asarray(qt), jnp.asarray(qh),
                                lookup_fn=cuckoo_lookup_arena_auto)
    np.testing.assert_array_equal(hit, np.asarray(got_k.hit))
    np.testing.assert_array_equal(gh, np.asarray(got_k.head))
    for f in ("bucket", "slot"):
        np.testing.assert_array_equal(np.asarray(getattr(got, f))[hit],
                                      np.asarray(getattr(got_k, f))[hit],
                                      err_msg=f"kernel probe {f}")
    print("bank-axis sharded lookup equivalence OK")
    """)


def test_bank_sharded_memory_fraction():
    """Acceptance: at T=256 on an 8-device mesh each device holds exactly
    1/8 of the replicated per-device filter-table bytes (sharding
    inspection on every table)."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import (build_forest, build_bank, sharded_lookup_bank,
                            stage_sharded_bank)
    from repro.core import hashing

    T, D = 256, 8
    trees = [[(f"r{t}", f"e{t}_{i}") for i in range(6)] for t in range(T)]
    forest = build_forest(trees)
    bank = build_bank(forest)
    sbank = bank.shard(D)
    mesh = jax.make_mesh((D,), ("model",))
    state = stage_sharded_bank(sbank, forest, mesh, "model")
    for arr in (state.fingerprints, state.temperature, state.heads):
        replicated = bank.total_buckets * bank.slots * arr.dtype.itemsize
        shards = list(arr.addressable_shards)
        assert len(shards) == D
        per_dev = {s.data.nbytes for s in shards}
        assert len(per_dev) == 1, "unbalanced shards"
        assert per_dev.pop() * D <= replicated, (arr.shape, replicated)
    # and the sharded state still answers: one hit per tree
    qt = np.arange(T, dtype=np.int32)
    qh = np.asarray([int(hashing.entity_hash(f"e{t}_0")) for t in range(T)],
                    np.uint32)
    got = sharded_lookup_bank(state, jnp.asarray(qt), jnp.asarray(qh))
    assert bool(np.asarray(got.hit).all())
    print("sharded memory fraction OK")
    """)


def test_sharded_maintenance_shard_local_churn():
    """Insert/delete/expand on one hot tree: non-owning shards'
    tables stay byte-identical, expand restages only the hot tree's
    arena segment (even the owner's other trees keep their bytes), and
    the maintained sharded bank answers identically to a from-scratch
    sharded build — including the heterogeneous per-tree-nb device
    lookup after the expansion."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import (build_forest, build_bank, build_bank_from_rows,
                            lookup_batch_ragged, ShardedMaintenanceEngine,
                            sharded_lookup_bank, stage_sharded_bank)
    from repro.core import hashing

    T, D = 16, 4
    trees = [[(f"r{t}", f"e{t}_{i}") for i in range(12)] for t in range(T)]
    forest = build_forest(trees)
    bank = build_bank(forest)
    sbank = bank.shard(D)
    eng = ShardedMaintenanceEngine(sbank)
    mesh = jax.make_mesh((D,), ("model",))
    TABLES = ("fingerprints", "temperature", "heads", "entity_ids",
              "stored_hash")

    hot = 9
    owner, hot_lt = sbank.owner(hot)
    others = [d for d in range(D) if d != owner]
    snap = {d: tuple(getattr(sbank.banks[d], f).tobytes() for f in TABLES)
            for d in others}
    nb_before = [b.tree_nb.copy() for b in sbank.banks]

    node_pool = sorted(sbank.banks[owner].walk_row(0))
    eng.queue_delete(hot, f"e{hot}_0")
    eng.queue_delete(hot, f"e{hot}_1")
    for k in range(3):
        eng.queue_insert(hot, f"new {hot}_{k}", node_pool[:2])
    rep = eng.maintain()
    assert rep.inserted == 3 and rep.deleted == 2, rep
    ob = sbank.banks[owner]
    cold_snap = {lt: tuple(
        arr[int(ob.bucket_offsets[lt]):int(ob.bucket_offsets[lt + 1])]
        .tobytes() for arr in (ob.fingerprints, ob.heads, ob.stored_hash))
        for lt in range(ob.num_trees) if lt != hot_lt}
    nb_mid = int(ob.tree_nb[hot_lt])
    assert eng.expand_tree(hot, force=True)
    assert int(ob.tree_nb[hot_lt]) == 2 * nb_mid
    # ... and within the owner, only the hot tree's segment changed
    assert (np.delete(ob.tree_nb, hot_lt)
            == np.delete(nb_before[owner], hot_lt)).all()
    for lt, s in cold_snap.items():
        cur = tuple(
            arr[int(ob.bucket_offsets[lt]):int(ob.bucket_offsets[lt + 1])]
            .tobytes() for arr in (ob.fingerprints, ob.heads,
                                   ob.stored_hash))
        assert cur == s, f"cold tree {lt} of the owner mutated"

    # expand + churn touched ONLY the owner: everyone else byte-equal
    for d in others:
        cur = tuple(getattr(sbank.banks[d], f).tobytes() for f in TABLES)
        assert cur == snap[d], f"non-owning shard {d} mutated"
        assert np.array_equal(sbank.banks[d].tree_nb, nb_before[d])

    # maintained sharded bank == from-scratch sharded build (answers)
    live = {}
    for t in range(T):
        for _, name in trees[t]:
            if t == hot and name in (f"e{hot}_0", f"e{hot}_1"):
                continue
            live[(t, name)] = bank.locate(t, name)
    for k in range(3):
        live[(hot, f"new {hot}_{k}")] = node_pool[:2]
    ks = sorted(live)
    rt = np.asarray([t for t, _ in ks], np.int32)
    rh = np.asarray([int(hashing.entity_hash(n)) for _, n in ks],
                    np.uint32)
    lens = np.asarray([len(live[k]) for k in ks], np.int32)
    off = np.zeros(len(ks) + 1, np.int32)
    np.cumsum(lens, out=off[1:])
    nodes = np.concatenate([np.asarray(live[k], np.int32) for k in ks])
    fresh = build_bank_from_rows(
        T, rt, np.full(len(ks), -1, np.int32), rh, off,
        nodes).shard(tree_starts=sbank.tree_starts)
    for (t, name), nl in live.items():
        assert sorted(sbank.locate(t, name)) == \
            sorted(fresh.locate(t, name)) == sorted(nl), (t, name)
    assert not sbank.contains(hot, int(hashing.entity_hash(f"e{hot}_0")))

    # device lookup on the heterogeneous per-tree-nb sharded bank:
    # per-shard ragged reference (each shard's own arena + offsets table)
    # matches bit-identically
    state = stage_sharded_bank(sbank, forest, mesh, "model")
    assert len(set(sbank.tree_nb_map().tolist())) > 1  # really ragged now
    qt = np.asarray([t for t, _ in ks], np.int32)
    qh = rh
    got = sharded_lookup_bank(state, jnp.asarray(qt), jnp.asarray(qh))
    base = sbank.shard_row_base()
    shard_of = sbank.tree_shard_map()
    local_of = sbank.tree_local_map()
    for d in range(D):
        sel = shard_of[qt] == d
        if not sel.any():
            continue
        b = sbank.banks[d]
        occ = b.fingerprints != hashing.EMPTY_FP
        heads_m = np.where(occ, b.heads + np.int32(base[d]), -1)
        ref = lookup_batch_ragged(
            jnp.asarray(b.fingerprints), jnp.asarray(heads_m),
            jnp.asarray(b.bucket_offsets.astype(np.int32)),
            jnp.asarray(b.tree_nb),
            jnp.asarray(local_of[qt[sel]]), jnp.asarray(qh[sel]))
        for f in ("hit", "head", "bucket", "slot"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ref, f)),
                np.asarray(getattr(got, f))[sel], err_msg=f)
    gh = np.asarray(got.head)
    assert bool(np.asarray(got.hit).all())
    for j, k in enumerate(ks):
        assert sorted(sbank.walk_row(int(gh[j]))) == sorted(live[k])
    print("shard-local maintenance churn OK")
    """)


def test_sharded_temperature_absorb_no_double_count():
    """Temperature feedback under sharding: two serve+maintain cycles pin
    the exact bump totals — each slot's bumps harvested once against the
    owning shard's baseline, padding rows/buckets never counted, repeated
    absorb of an unchanged device state adds zero."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import (build_forest, build_bank,
                            ShardedMaintenanceEngine,
                            sharded_retrieve_device, stage_sharded_bank)
    from repro.core import hashing

    T, D = 10, 4            # ragged partition -> padded rows exist
    trees = [[(f"r{t}", f"e{t}_{i}") for i in range(8)] for t in range(T)]
    forest = build_forest(trees)
    bank = build_bank(forest)
    sbank = bank.shard(D)
    assert sbank.arena_rows_per_shard * D > sbank.total_buckets, \
        "need packed-arena padding for this test"
    eng = ShardedMaintenanceEngine(sbank)
    mesh = jax.make_mesh((D,), ("model",))
    state = stage_sharded_bank(sbank, forest, mesh, "model")

    # every stored entity once, plus misses; B=87 pads internally
    qt = np.asarray([t for t in range(T) for _ in range(8)] + [3] * 7,
                    np.int32)
    qh = np.asarray(
        [int(hashing.entity_hash(f"e{t}_{i}"))
         for t in range(T) for i in range(8)]
        + [int(hashing.entity_hash(f"nope {j}")) for j in range(7)],
        np.uint32)

    totals = 0
    for cycle in range(2):
        out = sharded_retrieve_device(state, jnp.asarray(qh),
                                      jnp.asarray(qt))
        hits = int(np.asarray(out.hit).sum())
        assert hits == 8 * T, hits
        state = state.with_temperature(out.temperature)
        rep = eng.maintain(state)
        totals += hits
        assert rep.absorbed_bumps == hits, (cycle, rep.absorbed_bumps,
                                            hits)
        host_total = sum(int(b.temperature.sum()) for b in sbank.banks)
        assert host_total == totals, (cycle, host_total, totals)
        # re-absorbing the same device state must add nothing
        assert eng.absorb(state) == 0
        if rep.changed:           # sort may have fired: restage
            state = stage_sharded_bank(sbank, forest, mesh, "model")
    # per-tree pinning: each tree absorbed exactly 2 * its query hits
    for t in range(T):
        d, lt = sbank.owner(t)
        b = sbank.banks[d]
        lo, hi = int(b.bucket_offsets[lt]), int(b.bucket_offsets[lt + 1])
        tree_total = int(b.temperature[lo:hi].sum())
        assert tree_total == 2 * 8, (t, tree_total)
    print("sharded temperature absorb OK")
    """)


def test_two_pass_capacity():
    """Two-pass count-then-exchange capacity: balanced loads answer
    bit-identically through the factor-sized (fast path) buffer, the
    count pass reports exact per-pair routing, and an adversarial batch
    that overflowed the old eager pre-check (every query to one shard)
    now adapts the buffer to the measured maximum and answers exactly —
    no raise, no dropped queries."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import (build_forest, build_bank, routing_counts,
                            sharded_lookup_bank, sharded_retrieve_device,
                            stage_sharded_bank)
    from repro.core.distributed import _pick_capacity
    from repro.core import hashing

    T, D = 32, 8
    trees = [[(f"r{t}", f"e{t}_{i}") for i in range(6)] for t in range(T)]
    forest = build_forest(trees)
    bank = build_bank(forest)
    sbank = bank.shard(D)
    mesh = jax.make_mesh((D,), ("model",))
    state = stage_sharded_bank(sbank, forest, mesh, "model")

    # balanced: round-robin trees -> per-(src, dst) load is B/(D*D)
    qt = (np.arange(128) % T).astype(np.int32)
    qh = np.asarray([int(hashing.entity_hash(f"e{t}_0")) for t in qt],
                    np.uint32)
    counts = routing_counts(state, qt)
    assert counts.shape == (D, D) and counts.sum() == 128
    # each source's 16 round-robin queries cover 16 consecutive trees =
    # 4 shards at 4 queries each (pads included) -- the counts are exact
    assert counts.max() == 4, counts
    full = sharded_lookup_bank(state, jnp.asarray(qt), jnp.asarray(qh))
    half = sharded_lookup_bank(state, jnp.asarray(qt), jnp.asarray(qh),
                               capacity_factor=0.5)
    for f in ("hit", "head", "bucket", "slot"):
        np.testing.assert_array_equal(np.asarray(getattr(full, f)),
                                      np.asarray(getattr(half, f)),
                                      err_msg=f"capacity_factor {f}")
    assert bool(np.asarray(half.hit).all())
    # fast path: counts fit, so the factor sizes the (shrunken) buffer
    cap = _pick_capacity(state, qt, 0.5)
    assert cap == 8 and cap < 128 // D, cap

    # retrieve path threads the factor too
    out = sharded_retrieve_device(state, jnp.asarray(qh), jnp.asarray(qt),
                                  capacity_factor=0.5)
    assert bool(np.asarray(out.hit).all())

    # adversarial: every query to shard 0's trees overflowed the old
    # eager check at factor 0.25 -- the second pass now sizes the buffer
    # from the measured max and the batch answers bit-identically
    qt_bad = np.zeros(64, np.int32)
    assert int(routing_counts(state, qt_bad).max()) == 64 // D
    cap_bad = _pick_capacity(state, qt_bad, 0.25)
    assert cap_bad == 64 // D, cap_bad          # adapted past ceil(f*Bl)
    ref = sharded_lookup_bank(state, jnp.asarray(qt_bad),
                              jnp.asarray(qh[:64]))
    got = sharded_lookup_bank(state, jnp.asarray(qt_bad),
                              jnp.asarray(qh[:64]), capacity_factor=0.25)
    for f in ("hit", "head", "bucket", "slot"):
        np.testing.assert_array_equal(np.asarray(getattr(ref, f)),
                                      np.asarray(getattr(got, f)),
                                      err_msg=f"adaptive {f}")
    print("two-pass capacity OK")
    """)


def test_sharded_splice_commit_matches_from_scratch():
    """Acceptance gate (sharded): across random churn schedules
    (insert/delete/expand/shrink), plan_restage + commit_restage leaves
    the packed ShardedBankState byte-identical to a from-scratch
    stage_sharded_bank — and a splice-only cycle never writes a
    non-owning shard's block (device buffers compared byte-for-byte)."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import (ShardedMaintenanceEngine, build_bank,
                            build_forest, commit_restage,
                            sharded_retrieve_device, stage_sharded_bank)
    from repro.core import hashing

    T, D = 16, 4
    FIELDS = ("fingerprints", "temperature", "heads", "tree_shard",
              "tree_offset", "tree_nb", "csr_offsets", "csr_nodes")

    def shard_bytes(state, d):
        ap = state.arena_rows_per_shard
        return tuple(np.asarray(getattr(state, f))[d * ap:(d + 1) * ap]
                     .tobytes() for f in ("fingerprints", "temperature",
                                          "heads"))

    for seed in (0, 7):
        rng = np.random.default_rng(seed)
        trees = [[(f"r{t}", f"e{t}_{i}") for i in range(12)]
                 for t in range(T)]
        forest = build_forest(trees)
        bank = build_bank(forest)
        sbank = bank.shard(D)
        eng = ShardedMaintenanceEngine(sbank, seed=seed)
        mesh = jax.make_mesh((D,), ("model",))
        state = stage_sharded_bank(sbank, forest, mesh, "model")
        eng.mark_staged()
        serial = 0
        for cycle in range(4):
            # churn one shard's trees only, so the others must stay
            # byte-identical through the splice commit
            hot_shard = int(rng.integers(D))
            lo, hi = (int(sbank.tree_starts[hot_shard]),
                      int(sbank.tree_starts[hot_shard + 1]))
            for _ in range(int(rng.integers(2, 6))):
                t = int(rng.integers(lo, hi))
                if rng.random() < 0.6:
                    eng.queue_insert(t, f"new {seed} {serial}", [serial])
                    serial += 1
                else:
                    eng.queue_delete(t, f"e{t}_{int(rng.integers(12))}")
            eng.maintain()
            if rng.random() < 0.5:
                eng.expand_tree(int(rng.integers(lo, hi)), force=True)
            elif rng.random() < 0.5:
                eng.shrink_tree(int(rng.integers(lo, hi)), force=True)
            before = {d: shard_bytes(state, d) for d in range(D)
                      if d != hot_shard}
            plan = eng.plan_restage()
            state2 = commit_restage(state, plan, eng, forest)
            ref = stage_sharded_bank(sbank, forest, mesh, "model",
                                     arena_rows=state2.arena_rows_per_shard)
            for f in FIELDS:
                np.testing.assert_array_equal(
                    np.asarray(getattr(state2, f)),
                    np.asarray(getattr(ref, f)),
                    err_msg=f"seed {seed} cycle {cycle} {plan.kind}: {f}")
            in_place = (plan.kind == "splice"
                        and state2.arena_rows_per_shard
                        == state.arena_rows_per_shard)
            if in_place:   # else: segment outgrew the padding -> repack
                for d, b in before.items():
                    # shards before the churned one are always untouched;
                    # later shards too unless an insert shifted their
                    # merged head numbering (zero host bytes either way)
                    if d < hot_shard or plan.head_shift is None:
                        assert shard_bytes(state2, d) == b, \
                            (seed, cycle, d, "non-owner block mutated")
            state = state2
            # committed state serves: every surviving key resolves
            qt = np.asarray([t for t in range(T)], np.int32)
            qh = np.asarray([int(hashing.entity_hash(f"e{t}_2"))
                             for t in range(T)], np.uint32)
            out = sharded_retrieve_device(state, jnp.asarray(qh),
                                          jnp.asarray(qt))
            state = state.with_temperature(out.temperature)
            eng.absorb(state)
    print("sharded splice commit OK")
    """, devices=4)


def test_small_mesh_train_step_sharded():
    """Sharded train step == single-device train step (tiny dense model)."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_arch
    from repro.models import init_params, runtime
    from repro.training import AdamWConfig, adamw_init, make_train_step
    from repro.launch import sharding as sh
    from repro.launch.mesh import make_test_mesh

    # capacity_factor high enough that no tokens drop: per-shard capacity
    # (sharded path) and global capacity (local path) then agree exactly
    cfg = get_arch("granite-moe-1b-a400m").smoke().replace(
        d_model=128, num_experts=4, top_k=2, capacity_factor=8.0)
    params = init_params(cfg, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    toks = jax.random.randint(key, (8, 32), 4, cfg.vocab)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1),
             "mask": jnp.ones((8, 32), jnp.float32)}
    ocfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=4)

    p1, _, m1 = make_train_step(cfg, ocfg)(params, adamw_init(params), batch)

    mesh = make_test_mesh(2, 4)
    runtime.set_mesh(mesh, ("data",))
    params_sh = sh.params_shardings(mesh, jax.eval_shape(lambda: params))
    opt_abs = jax.eval_shape(adamw_init, params)
    opt_sh = sh.opt_shardings(mesh, opt_abs, params_sh)
    bs = jax.tree.map(lambda t: NamedSharding(
        mesh, P("data", *(None,) * (t.ndim - 1))), batch)
    step = make_train_step(cfg, ocfg, param_shardings=params_sh,
                           data_axes=("data",))
    with jax.set_mesh(mesh):
        fn = jax.jit(step, in_shardings=(params_sh, opt_sh, bs),
                     out_shardings=(params_sh, opt_sh, None))
        p2, _, m2 = fn(jax.device_put(params, params_sh),
                       jax.device_put(adamw_init(params), opt_sh),
                       jax.device_put(batch, bs))
    runtime.clear_mesh()
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-3, \
        (float(m1["loss"]), float(m2["loss"]))
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=3e-2, rtol=3e-2)
    print("sharded train step OK")
    """)


def test_elastic_checkpoint_restore_across_meshes():
    """Save on a (2,4) mesh, restore onto (4,2) — elastic re-shard."""
    _run("""
    import tempfile, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_arch
    from repro.models import init_params
    from repro.training import adamw_init, restore, save
    from repro.launch import sharding as sh

    cfg = get_arch("qwen2-0.5b").smoke()
    params = init_params(cfg, jax.random.PRNGKey(0))
    mesh_a = jax.make_mesh((2, 4), ("data", "model"))
    mesh_b = jax.make_mesh((4, 2), ("data", "model"))
    sh_a = sh.params_shardings(mesh_a, jax.eval_shape(lambda: params))
    sh_b = sh.params_shardings(mesh_b, jax.eval_shape(lambda: params))
    placed = jax.device_put(params, sh_a)
    with tempfile.TemporaryDirectory() as d:
        save(d, 1, {"params": placed})
        got, step, _ = restore(d, {"params": params},
                               shardings={"params": sh_b})
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(got["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("elastic restore OK")
    """)


def test_moe_small_batch_token_routing():
    """Decode-scale MoE: token-routed path == local path (weights resident)."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_arch
    from repro.models import moe as M

    cfg = get_arch("granite-moe-1b-a400m").smoke().replace(
        d_model=64, num_experts=8, top_k=2, d_ff=32, capacity_factor=8.0,
        shared_expert=True)
    p = M.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 1, 64), jnp.float32)
    y_local = M._moe_apply_local(cfg, p, x)
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    with jax.set_mesh(mesh):
        y_small = M._moe_small_batch(cfg, p, x, mesh, ("data",), "model", 2)
    np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_small),
                               atol=2e-5, rtol=2e-5)
    print("token-routed MoE OK")
    """)


def test_mini_dryrun_multi_pod_mesh():
    """A miniature multi-pod mesh (2,2,2) lower+compile for a smoke arch —
    proves the pod axis shards end to end without the 512-device cost."""
    _run("""
    import jax, jax.numpy as jnp
    from repro.configs import get_arch, SHAPES
    from repro.launch import sharding as sh, specs
    from repro.launch.mesh import make_test_mesh
    from repro.models import lm, runtime
    from repro.training.grad import make_train_step
    from repro.training.optimizer import AdamWConfig, adamw_init
    import dataclasses

    mesh = make_test_mesh(2, 2, pod=2)
    runtime.set_mesh(mesh, ("pod", "data"))
    cfg = get_arch("qwen2-0.5b").smoke()
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64,
                                global_batch=8)
    params_abs = specs.params_specs(cfg)
    params_sh = sh.params_shardings(mesh, params_abs)
    with jax.set_mesh(mesh):
        opt_abs = jax.eval_shape(adamw_init, params_abs)
        opt_sh = sh.opt_shardings(mesh, opt_abs, params_sh)
        batch_abs = specs.train_batch_specs(cfg, shape)
        batch_sh = sh.batch_shardings(mesh, cfg, shape, batch_abs)
        step = make_train_step(cfg, AdamWConfig(), microbatches=2,
                               param_shardings=params_sh,
                               data_axes=("pod", "data"))
        c = jax.jit(step, in_shardings=(params_sh, opt_sh, batch_sh),
                    out_shardings=(params_sh, opt_sh, None)
                    ).lower(params_abs, opt_abs, batch_abs).compile()
    assert c.memory_analysis() is not None
    print("mini multi-pod dryrun OK")
    """, devices=8)


def test_sharded_tenant_evict_reload_bit_exact():
    """Cold-tenant eviction over a bank-axis sharded deployment: evicting
    a single-shard tenant touches only its owning shard (every other
    shard byte-identical), a tenant spanning two shards splices per
    owning piece, and reload restores every shard's tables bit-exactly —
    the sharded device lookup answers match the pre-eviction baseline
    field for field.  Shard boundaries come from the tenant-aligned
    planner, so no tenant straddles a shard it doesn't own outright."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import (TenantRegistry, build_forest, build_bank,
                            ShardedMaintenanceEngine, plan_tenant_partition,
                            sharded_lookup_bank, stage_sharded_bank)
    from repro.core import hashing

    T, D = 8, 4
    trees = [[(f"r{t}", f"e{t}_{i}") for i in range(10)] for t in range(T)]
    forest = build_forest(trees)
    bank = build_bank(forest)
    reg = TenantRegistry({"a": (0, 2), "b": (2, 4), "c": (4, 6),
                          "d": (6, 8)})
    starts = plan_tenant_partition(bank.tree_nb, reg, D)
    for name in reg.names:                 # planner honors every boundary
        lo, hi = reg.trees(name)
        assert not any(int(lo) < int(s) < int(hi) for s in starts), name
    sbank = bank.shard(tree_starts=starts)
    mesh = jax.make_mesh((D,), ("model",))
    TABLES = ("fingerprints", "temperature", "heads", "entity_ids",
              "stored_hash")

    def shard_bytes(d):
        return tuple(getattr(sbank.banks[d], f).tobytes() for f in TABLES)

    def answers():
        state = stage_sharded_bank(sbank, forest, mesh, "model")
        got = sharded_lookup_bank(state, jnp.asarray(qt), jnp.asarray(qh))
        return {f: np.asarray(getattr(got, f)).copy()
                for f in ("hit", "head", "bucket", "slot")}

    qt = np.asarray([t for t in range(T) for _ in range(10)], np.int32)
    qh = np.asarray([int(hashing.entity_hash(f"e{t}_{i}"))
                     for t in range(T) for i in range(10)], np.uint32)
    base = answers()
    assert base["hit"].all()
    snap = {d: shard_bytes(d) for d in range(D)}

    # --- single-shard tenant: surgery stays inside the owning shard
    blo, bhi = reg.trees("b")
    owners = [d for d in range(D)
              if max(blo, int(starts[d])) < min(bhi, int(starts[d + 1]))]
    assert len(owners) == 1
    cold = reg.evict(sbank, "b")
    eng = ShardedMaintenanceEngine(sbank)
    eng.pin_tree_range(blo, bhi, True)
    try:
        eng.queue_insert(blo, "blocked", [0])
        raise SystemExit("pinned insert must raise")
    except ValueError:
        pass
    for d in range(D):
        if d not in owners:
            assert shard_bytes(d) == snap[d], f"shard {d} mutated"
    mid = answers()
    sel = (qt >= blo) & (qt < bhi)
    assert not mid["hit"][sel].any()       # the cold tenant misses
    for f in ("hit", "head", "bucket", "slot"):   # everyone else exact
        np.testing.assert_array_equal(mid[f][~sel], base[f][~sel],
                                      err_msg=f)
    reg.reload(sbank, "b")
    eng.pin_tree_range(blo, bhi, False)
    for d in range(D):
        assert shard_bytes(d) == snap[d], f"shard {d} not restored"

    # --- a tenant spanning two shards splices per owning piece
    wide = TenantRegistry({"w": (0, 4), "c": (4, 6), "d": (6, 8)})
    cold_w = wide.evict(sbank, "w")
    assert cold_w.arena_rows > 0
    changed = [d for d in range(D) if shard_bytes(d) != snap[d]]
    assert changed == [d for d in range(D)
                       if max(0, int(starts[d])) < min(4, int(starts[d + 1]))]
    assert len(changed) == 2
    assert not answers()["hit"][qt < 4].any()
    wide.reload(sbank, "w")
    for d in range(D):
        assert shard_bytes(d) == snap[d], f"shard {d} not restored (wide)"
    post = answers()
    for f in ("hit", "head", "bucket", "slot"):
        np.testing.assert_array_equal(post[f], base[f], err_msg=f)
    print("sharded tenant evict/reload OK")
    """, devices=4)


def test_sharded_fused_owner_probe_byte_equality():
    """The fused owner-shard probe (probe + bump + CSR window in one
    Pallas launch before the route-back) is byte-identical to the unfused
    sharded path — hit/locations/hierarchy and the *sharded-layout*
    temperature, across rounds and both capacity modes."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import build_forest, build_bank, stage_sharded_bank
    from repro.core.distributed import sharded_retrieve_device
    from repro.core import hashing

    T, D = 32, 8
    trees = [[(f"r{t}", f"e{t}_{i}") for i in range(4 + (t % 5) * 3)]
             for t in range(T)]
    for t in range(0, T, 4):                       # deepen a few trees
        trees[t] += [(f"e{t}_0", f"e{t}_c{j}") for j in range(5)]
    forest = build_forest(trees)
    bank = build_bank(forest)
    sbank = bank.shard(D)
    mesh = jax.make_mesh((D,), ("model",))
    rng = np.random.default_rng(1)
    qt = [t for t in range(T) for _ in range(3)] + \\
         [int(rng.integers(T)) for _ in range(15)] + [-3, T + 9]
    qh = [int(hashing.entity_hash(f"e{t}_{k}"))
          for t in range(T) for k in (0, 1, 2)] + \\
         [int(rng.integers(1, 2 ** 32)) for _ in range(17)]
    qt = jnp.asarray(np.asarray(qt, np.int32))
    qh = jnp.asarray(np.asarray(qh, np.uint32))

    for cf in (None, 0.5):
        s_ref = stage_sharded_bank(sbank, forest, mesh, "model")
        s_fus = stage_sharded_bank(sbank, forest, mesh, "model")
        for rnd in range(3):
            ref = sharded_retrieve_device(s_ref, qh, qt,
                                          capacity_factor=cf)
            got = sharded_retrieve_device(s_fus, qh, qt,
                                          capacity_factor=cf, fused=True)
            for f in ("hit", "locations", "up", "down", "temperature"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(ref, f)),
                    np.asarray(getattr(got, f)),
                    err_msg=f"{f} cf={cf} round={rnd}")
            s_ref = s_ref.with_temperature(ref.temperature)
            s_fus = s_fus.with_temperature(got.temperature)
    assert np.asarray(ref.hit)[:3 * T].all()
    assert not np.asarray(ref.hit)[-2:].any()      # out-of-range ids miss
    print("sharded fused owner probe OK")
    """)
