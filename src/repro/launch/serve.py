"""RAG serving driver: build the CFT index over a corpus and answer queries.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --trees 100 --queries 4
"""
from __future__ import annotations

import argparse
import time

import jax

from ..configs import get_arch
from ..data import HashTokenizer, hospital_corpus
from ..models import init_params
from ..serving import RAGPipeline, ServeEngine
from .compile_cache import configure_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--trees", type=int, default=100)
    ap.add_argument("--queries", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--cache", type=int, default=256)
    ap.add_argument("--device-lookup", action="store_true")
    args = ap.parse_args()
    configure_compile_cache()

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    params = init_params(cfg, jax.random.PRNGKey(0))
    corpus = hospital_corpus(num_trees=args.trees, num_queries=args.queries)
    engine = ServeEngine(cfg, params, cache_size=args.cache)
    rag = RAGPipeline(corpus, engine, tokenizer=HashTokenizer(cfg.vocab),
                      use_device_lookup=args.device_lookup)

    for q in corpus.queries[:args.queries]:
        t0 = time.perf_counter()
        ans = rag.answer(q, max_new_tokens=args.max_new)
        dt = time.perf_counter() - t0
        print(f"\nQ: {q[:90]}...")
        print(f"  entities: {ans.entities}")
        print(f"  context:  {ans.context.splitlines()[:2]} ...")
        print(f"  out ids:  {ans.output_ids}  ({dt*1e3:.0f} ms)")
    acc = rag.retrieval_accuracy(corpus.queries[:args.queries],
                                 corpus.query_entities[:args.queries])
    print(f"\nretrieval accuracy proxy: {acc:.4f}")


if __name__ == "__main__":
    main()
