"""Distributed retrieval: the cuckoo filter sharded across a device mesh,
with queries resolved by the shard_map lookup (pod-scale retrieval path).

Shards over every device JAX finds (one shard per device).  On a CPU,
ask for several host devices to see the sharding:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/distributed_lookup.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import build_forest, build_index, lookup_batch
from repro.core import hashing
from repro.core.distributed import shard_filter_tables, sharded_lookup
from repro.data import hospital_corpus


def main():
    corpus = hospital_corpus(num_trees=200)
    forest = build_forest(corpus.trees)
    d = len(jax.devices())          # a power of two divides the buckets
    index = build_index(forest, num_buckets=2048)
    t = index.filter.tables()

    mesh = jax.make_mesh((d,), ("model",))
    fps, heads = shard_filter_tables(mesh, "model",
                                     jnp.asarray(t.fingerprints),
                                     jnp.asarray(t.heads))
    print(f"filter sharded over {mesh.shape['model']} shards x "
          f"{index.filter.num_buckets // mesh.shape['model']} buckets")

    names = forest.entity_names[:96] + ["Missing Unit X"]
    h = jnp.asarray(hashing.hash_entities(names))
    got = sharded_lookup(mesh, "model", fps, heads, h)
    ref = lookup_batch(jnp.asarray(t.fingerprints), jnp.asarray(t.heads), h)
    assert np.array_equal(np.asarray(got.hit), np.asarray(ref.hit))
    assert np.array_equal(np.asarray(got.head), np.asarray(ref.head))
    print(f"sharded lookup == replicated lookup on {len(names)} queries "
          f"({int(np.asarray(got.hit).sum())} hits)")


if __name__ == "__main__":
    main()
