"""Faults planted under the timed path, for the harness's own tests:
each must turn ``correct`` false.

* ``answer_altered``: one answer changed where it is produced (the first
  query of every batch answers the next node);
* ``half_batch``: the second half of every batch left out (answered as
  misses);
* ``token_altered``: the first served token of every answer changed;
* ``short_walk``: the hierarchy walks cut one step short (the last
  ancestor and descendant left out), the retrieval cells' control;
* ``fp8_control``: the generator's control, the float8 reference in the
  program's place (``entries/rag_answer.py``).
"""
from __future__ import annotations


FAULTS = ("answer_altered", "half_batch", "token_altered",
          "short_walk", "fp8_control")


def _break(out, fault: str):
    import jax.numpy as jnp
    if fault == "answer_altered":
        return out._replace(locations=out.locations.at[0, 0].add(1))
    if fault == "half_batch":
        b = out.hit.shape[0]
        keep = jnp.arange(b) < b // 2
        null = lambda x: jnp.where(  # noqa: E731
            keep.reshape((b,) + (1,) * (x.ndim - 1)), x, -1)
        return out._replace(hit=out.hit & keep,
                            locations=null(out.locations),
                            up=null(out.up), down=null(out.down))
    if fault == "short_walk":
        return out._replace(up=out.up.at[..., -1].set(-1),
                            down=out.down.at[..., -1].set(-1))
    return out


def plant_retrieval(session, fault: str) -> None:
    """Break the session's retrieval step (the async engine's path)."""
    step = session._step
    session._step = lambda state, hh, tid: _break(step(state, hh, tid),
                                                  fault)


def plant_rag(entry, fault: str) -> None:
    """Break ``RAGPipeline``'s device retrieval, or its generator."""
    import repro.serving.rag as rag_mod
    if fault == "fp8_control":
        return
    if fault == "token_altered":
        engine = entry.rag.engine
        generate = engine.generate

        def altered(batch, max_new):
            out = generate(batch, max_new).copy()
            out[:, 0] = (out[:, 0] + 1) % engine.cfg.vocab
            return out
        engine.generate = altered
        return
    orig = rag_mod.retrieve_device
    rag_mod.retrieve_device = lambda *a, **kw: _break(orig(*a, **kw), fault)
    entry.unplant = lambda: setattr(rag_mod, "retrieve_device", orig)


