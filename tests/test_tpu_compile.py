"""Compile-only checks for a TPU v5e: the main-path retrieval kernels at the
600-tree hospital geometry and one full-width ``paper-cftrag`` decode step
compile for a described (not attached) v5e chip.  Nothing runs, so these
say nothing about results or times; they catch what the chip's compiler
refuses (unsupported casts, SMEM loads, VMEM overflow) at no chip time.

The topology is described inside a module fixture, never while a module is
imported: only one process may load the TPU library at a time.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# the 600-tree hospital_corpus bank (CFTDeviceState.from_bank shapes)
ARENA, SLOTS, TREES = 4500, 4, 600
CSR_ROWS, CSR_NODES, NODES, CHILDREN = 16383, 16384, 10523, 9923
BATCH = 256
# v5e VMEM per core (jax.experimental.pallas.tpu.get_tpu_info, "TPU v5
# lite"): the scoped limit the kernels launch with on that chip
V5E_VMEM = 128 * 1024 * 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                              # pragma: no cover
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such compiles cannot be read back from a persistent cache off the chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _arena(sh, rows=ARENA):
    return (_spec(sh, (rows, SLOTS), jnp.uint32),      # fingerprints
            _spec(sh, (rows, SLOTS), jnp.int32))       # heads


def _queries(sh):
    return _spec(sh, (BATCH,), jnp.int32), _spec(sh, (BATCH,), jnp.uint32)


@pytest.mark.parametrize("rows,row_tile", [(ARENA, 0), (65536, 8192)],
                         ids=["single_block", "tiled"])
def test_arena_probe_compiles(one_chip, rows, row_tile):
    from repro.kernels.cuckoo_lookup.ops import cuckoo_lookup_arena
    fps, heads = _arena(one_chip, rows)
    off, h = _queries(one_chip)
    mask = _spec(one_chip, (BATCH,), jnp.uint32)
    fn = functools.partial(cuckoo_lookup_arena, interpret=False,
                           row_tile=row_tile, vmem_limit=V5E_VMEM)
    assert "tpu_custom_call" in _compile(fn, fps, heads, off, mask, h)


def test_tree_routed_probe_compiles(one_chip):
    from repro.kernels.cuckoo_lookup.ops import cuckoo_lookup_ragged
    fps, heads = _arena(one_chip)
    tid, h = _queries(one_chip)
    fn = functools.partial(cuckoo_lookup_ragged, interpret=False,
                           row_tile=0, vmem_limit=V5E_VMEM)
    assert "tpu_custom_call" in _compile(
        fn, fps, heads, _spec(one_chip, (TREES + 1,), jnp.int32),
        _spec(one_chip, (TREES,), jnp.int32), tid, h)


@pytest.mark.parametrize("row_tile", [0, 1024], ids=["single_block", "tiled"])
def test_fused_kernel_compiles(one_chip, row_tile):
    from repro.kernels.fused_retrieve.ops import fused_retrieve_ragged
    fps, heads = _arena(one_chip)
    tid, h = _queries(one_chip)
    i32 = functools.partial(_spec, one_chip, dtype=jnp.int32)
    fn = functools.partial(fused_retrieve_ragged, interpret=False, mxu=True,
                           row_tile=row_tile, vmem_limit=V5E_VMEM)
    assert "tpu_custom_call" in _compile(
        fn, fps, i32((ARENA, SLOTS)), heads, i32((TREES + 1,)),
        i32((TREES,)), tid, h, i32((CSR_ROWS + 1,)), i32((CSR_NODES,)),
        i32((NODES,)), i32((NODES,)), i32((NODES + 1,)), i32((CHILDREN,)))


def test_fused_probe_locs_compiles(one_chip):
    from repro.kernels.fused_retrieve.ops import fused_probe_locs
    fps, heads = _arena(one_chip)
    off, h = _queries(one_chip)
    i32 = functools.partial(_spec, one_chip, dtype=jnp.int32)
    fn = functools.partial(fused_probe_locs, interpret=False, mxu=True,
                           vmem_limit=V5E_VMEM)
    assert "tpu_custom_call" in _compile(
        fn, fps, i32((ARENA, SLOTS)), heads, off,
        _spec(one_chip, (BATCH,), jnp.uint32), i32((BATCH,)), h,
        i32((CSR_ROWS + 1,)), i32((CSR_NODES,)))


def test_paper_generator_decode_step_compiles(one_chip):
    """One greedy decode step of the full-width generator (24 layers,
    d=896, bf16) for a batch of 4 against a 512-slot cache."""
    from repro.configs import get_arch
    from repro.models import abstract_params, decode_step, init_decode_state
    cfg = get_arch("paper-cftrag")
    place = lambda t: jax.tree.map(                         # noqa: E731
        lambda x: _spec(one_chip, x.shape, x.dtype), t)
    params = place(abstract_params(cfg))
    state = place(jax.eval_shape(
        lambda p: init_decode_state(cfg, p, 4, 512), params))
    tok = _spec(one_chip, (4, 1), jnp.int32)
    compiled = jax.jit(functools.partial(decode_step, cfg)).lower(
        params, tok, state).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 16 * 1024 ** 3      # one v5e's HBM


def _loop_copy_bytes(hlo: str):
    """Bytes of each ``copy`` op inside the entry's while loops, in the
    loop bodies and every computation they call."""
    import re
    import numpy as np
    comps, entry, name = {}, None, None
    for line in hlo.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            head = line.split()
            name = (head[1] if head[0] == "ENTRY" else head[0]).lstrip("%")
            entry = name if head[0] == "ENTRY" else entry
            comps[name] = []
        elif name and line.startswith(" "):
            comps[name].append(line)
    seen = set()
    todo = [m for ins in comps[entry] if " while(" in ins
            for m in re.findall(r"body=%?([\w.\-]+)", ins)]
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for ins in comps[c]:
            todo += re.findall(
                r"(?:body|condition|calls|to_apply)=%?([\w.\-]+)", ins)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", ins):
                todo += [b.strip().lstrip("%") for b in group.split(",")]
    out = []
    for c in seen:
        for ins in comps[c]:
            m = re.search(r"= [a-z]+(\d*)\[([\d,]*)\]\S* copy(?:-done)?\(",
                          ins)
            if m:                                # bf16 -> 2 bytes, pred 1
                dims = [int(d) for d in m.group(2).split(",") if d]
                out.append(int(np.prod(dims)) * max(int(m.group(1) or 8) // 8,
                                                    1))
    return seen, out


def test_zamba2_decode_loop_keeps_state_and_weights_in_place(one_chip):
    """The serving engine's decode loop for Zamba2-7B's first pipeline
    stage at published widths (24 layers, bf16, batch 4, a 2,048-slot
    cache): every step runs inside one while loop, and nothing in it
    copies a KV cache, the SSM or conv state, or a weight; what copies
    remain are a step's activations and small vector prefetches."""
    from repro.configs import get_arch
    from repro.models import abstract_params, init_decode_state
    from repro.serving.engine import _decode_loop
    cfg = get_arch("zamba2-7b").replace(n_layers=24,
                                        hybrid_layers=(6, 11, 17, 23))
    place = lambda t: jax.tree.map(                         # noqa: E731
        lambda x: _spec(one_chip, x.shape, x.dtype), t)
    params = place(abstract_params(cfg))
    state = place(jax.eval_shape(
        lambda: init_decode_state(cfg, None, 4, 2048)))
    hlo = jax.jit(functools.partial(_decode_loop, cfg, 2048),
                  donate_argnums=(2,)).lower(
        params, _spec(one_chip, (4, 1), jnp.int32), state,
        _spec(one_chip, (), jnp.int32)).compile().as_text()
    body, copies = _loop_copy_bytes(hlo)
    assert body                                      # the loop is there
    assert max(copies, default=0) <= 64 * 1024, sorted(copies)[-4:]
