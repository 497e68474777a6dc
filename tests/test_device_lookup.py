"""Device-side batched lookup semantics vs host filter; temperature path;
the hierarchy walks against the host forest and the program they lower to."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CFTDeviceState, build_bank, build_forest,
                        build_index, bump_temperature_arena, lookup_arena,
                        retrieve_device, sort_buckets_arena)
from repro.core import hashing
from repro.core.context import gather_descendants, gather_hierarchy
from repro.core.trag import NULL
from repro.data import hospital_corpus


def _setup(trees=20):
    c = hospital_corpus(num_trees=trees)
    forest = build_forest(c.trees)
    idx = build_index(forest, num_buckets=1024)
    return c, forest, idx


def _one_segment(num_buckets, h):
    """A single filter is an arena of one segment: zero offsets, masks
    ``nb - 1``."""
    return (jnp.zeros(h.shape, jnp.int32),
            jnp.full(h.shape, num_buckets - 1, jnp.uint32))


def test_lookup_batch_matches_host():
    _, forest, idx = _setup()
    t = idx.filter.tables()
    names = forest.entity_names[:100] + [f"missing {i}" for i in range(20)]
    hs = hashing.hash_entities(names)
    h = jnp.asarray(hs)
    res = lookup_arena(jnp.asarray(t.fingerprints), jnp.asarray(t.heads),
                       *_one_segment(idx.filter.num_buckets, h), h)
    for i, nm in enumerate(names):
        hit, head = idx.filter.lookup(int(hs[i]), bump=False)
        assert bool(res.hit[i]) == hit, nm
        if hit:
            assert int(res.head[i]) == head, nm


def test_bump_and_sort_device():
    _, forest, idx = _setup(trees=5)
    t = idx.filter.tables()
    fps = jnp.asarray(t.fingerprints)
    temps = jnp.asarray(t.temperature)
    heads = jnp.asarray(t.heads)
    eids = jnp.asarray(t.entity_ids)
    h = jnp.asarray(hashing.hash_entities([forest.entity_names[3]] * 4))
    off, masks = _one_segment(idx.filter.num_buckets, h)
    res = lookup_arena(fps, heads, off, masks, h)
    temps2 = bump_temperature_arena(temps, off, res)
    assert int(temps2.sum()) == int(temps.sum()) + 4
    fps2, temps3, heads2, eids2 = sort_buckets_arena(fps, temps2, heads,
                                                     eids)
    # hot entity now at slot 0 of its bucket; membership preserved
    res2 = lookup_arena(fps2, heads2, off, masks, h)
    assert bool(res2.hit[0]) and int(res2.slot[0]) == 0
    assert int((fps2 != 0).sum()) == int((fps != 0).sum())


def test_retrieve_device_matches_host_contexts():
    _, forest, idx = _setup(trees=10)
    state = CFTDeviceState.from_index(idx)
    names = forest.entity_names[:32]
    hs = jnp.asarray(hashing.hash_entities(names))
    out = retrieve_device(state, hs, max_locs=6, n=3)
    for i, nm in enumerate(names):
        eid = forest.name_to_id[nm]
        gold_locs = sorted(n for _, n in forest.entity_locations[eid])[:6]
        got = sorted(int(v) for v in np.asarray(out.locations[i]) if v >= 0)
        assert got == gold_locs[:len(got)] and len(got) == min(6, len(gold_locs))
        # ancestors per location must match host walk
        for j, node in enumerate(np.asarray(out.locations[i])):
            if node < 0:
                continue
            up = [int(u) for u in np.asarray(out.up[i, j]) if u >= 0]
            assert up == forest.ancestors(int(node), 3)
            down = [int(dn) for dn in np.asarray(out.down[i, j]) if dn >= 0]
            assert down == forest.descendants(int(node), 3)


def _walk_forest():
    """Two trees: a root with 7 children (more than any walk's n), a chain
    6 deep under its first child, and a small second tree of leaves."""
    wide = [("hub", f"spoke {i}") for i in range(7)]
    chain = [("spoke 0", "c1")] + [(f"c{i}", f"c{i + 1}") for i in range(1, 6)]
    fan = [("c2", f"leaf {i}") for i in range(4)]
    other = [("root b", "b0"), ("root b", "b1"), ("b0", "b00")]
    return build_forest([wide + chain + fan, other])


@pytest.mark.parametrize("n", [1, 3, 5])
@pytest.mark.parametrize("walk", ["up", "down"])
def test_walks_match_host_forest(walk, n):
    """Every node, and NULL, walks to the host forest's ancestors (nearest
    first) or BFS descendants (level order), NULL-padded to n."""
    f = _walk_forest()
    assert max(len(f.children(i)) for i in range(f.num_nodes)) > 5
    nodes = np.append(np.arange(f.num_nodes, dtype=np.int32), NULL)
    if walk == "up":
        got = gather_hierarchy(jnp.asarray(f.parent),
                               jnp.asarray(f.entity_id),
                               jnp.asarray(nodes), n)
        host = f.ancestors
    else:
        got = gather_descendants(jnp.asarray(f.child_offsets),
                                 jnp.asarray(f.child_index),
                                 jnp.asarray(f.entity_id),
                                 jnp.asarray(nodes), n)
        host = f.descendants
    got = np.asarray(got)
    assert got.shape == (nodes.size, n)
    for i, node in enumerate(nodes):
        want = host(int(node), n) if node != NULL else []
        assert got[i].tolist() == want + [NULL] * (n - len(want)), node


def test_lowered_retrieval_step_has_no_loop_and_no_forest_copies():
    """The jitted retrieval step walks the hierarchy with no while loop and
    never materialises a (queries x forest) array: no op of the shape
    [B * max_locs, len(child_index)] or [B * max_locs, len(child_offsets)]."""
    c = hospital_corpus(num_trees=20)
    forest = build_forest(c.trees)
    state = CFTDeviceState.from_bank(build_bank(forest), forest)
    b, max_locs = 128, 4
    names = [forest.entity_names[i % forest.num_entities] for i in range(b)]
    hashes = jnp.asarray(hashing.hash_entities(names))
    trees = jnp.asarray(np.arange(b, dtype=np.int32) % forest.num_trees)
    step = jax.jit(functools.partial(retrieve_device, max_locs=max_locs, n=3))
    text = step.lower(state, hashes, trees).as_text()
    assert not re.search(r"\bwhile\b", text)
    flat = b * max_locs
    for width in (state.child_index.shape[0], state.child_offsets.shape[0]):
        assert f"tensor<{flat}x{width}x" not in text, width
