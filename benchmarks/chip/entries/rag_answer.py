"""Closed-loop RAG answers through ``RAGPipeline.answer``.

One client (a chat user) asks a query and waits for the generated answer
before asking the next: entity recognition, the fan-out retrieval over
every tree, the rendered prompt, greedy generation through ``ServeEngine``
and the pipeline's maintenance pass after the answer.  Each answer is
timed on the host clock from query text to tokens on the host; the entry
also times the pipeline's retrieve, serve and maintain calls inside it.

``ServeEngine.serve`` compiles a prefill for every new prompt length, so
set-up answers every pool query once, and once more for each length that
truncation to the cache can give a scheduled answer.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from entries import rag_retrieve, weights
from harness import Check


def _timed(fn, log, annotate, name):
    @functools.wraps(fn)
    def wrapper(*a, **kw):
        with annotate(name):
            t = time.perf_counter()
            try:
                return fn(*a, **kw)
            finally:
                log.append(time.perf_counter() - t)
    return wrapper


class Entry(rag_retrieve.Entry):
    kind = "answer"

    def build(self):
        import jax
        from repro.configs import get_arch
        from repro.data import HashTokenizer
        from repro.serving import RAGPipeline, ServeEngine

        ctx, m = self.ctx, self.ctx.config["model"]
        base = get_arch(ctx.config["program_arch"])
        cfg = base.replace(
            n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
            n_heads=m["num_attention_heads"],
            n_kv_heads=m["num_key_value_heads"],
            head_dim=m["hidden_size"] // m["num_attention_heads"],
            d_ff=m["intermediate_size"], vocab=m["vocab_size"],
            rope_theta=m["rope_theta"],
            tie_embeddings=m["tie_word_embeddings"],
            dtype=m["torch_dtype"])
        if cfg != base and not ctx.config.get("test_size"):
            raise ValueError(f"the program's {base.arch_id} differs from the "
                             f"configuration: {base} vs {cfg}")
        self.model_cfg = cfg
        self.weights = weights.make(m, ctx.key_seed)
        jax.block_until_ready(self.weights)
        s = ctx.config["serving"]
        engine = ServeEngine(cfg, self.weights, cache_size=s["cache_size"],
                             batch_size=s["batch_size"])
        rag = RAGPipeline(self.corpus, engine,
                          tokenizer=HashTokenizer(cfg.vocab), use_bank=True)
        self.t_retrieve, self.t_serve, self.t_maintain = [], [], []
        self.served = []
        serve = engine.serve

        def capture(requests):
            out = serve(requests)
            self.served.extend(requests)
            return out
        engine.serve = _timed(capture, self.t_serve, ctx.annotate, "serve")
        rag.retrieve = _timed(rag.retrieve, self.t_retrieve, ctx.annotate,
                              "retrieve")
        rag.maintain = _timed(rag.maintain, self.t_maintain, ctx.annotate,
                              "maintain")
        return rag

    def warm(self) -> None:
        """One whole answer (retrieval, prefill, decode, maintenance),
        then a prefill for every other prompt length the schedule can
        give: each of its queries' prompts, cut as ``ServeEngine.serve``
        cuts it to leave room for each answer length of the mix.

        The schedule's first block holds whole periods of the pool, so
        every query a later block asks; a later block may pair a query
        with an answer length that the first block does not, and the cut
        prompt of every such pair is warmed here too.  (On 200 seeds the
        first block's own pairs gave every one of these lengths.)"""
        from repro.serving.engine import Request
        sched, rag = self.schedule, self.rag
        cache = self.ctx.config["serving"]["cache_size"]
        rag.answer(sched.queries[0], max_new_tokens=int(sched.max_new[0]))
        seen = {len(self.served[0].prompt_ids)}
        for q in dict.fromkeys(sched.queries):
            prompt = rag.tokenizer.encode(rag.retrieve(q).prompt, bos=True)
            for m in sorted(set(self.ctx.traffic["answer_tokens"])):
                ids = prompt[-(cache - m):]
                if len(ids) not in seen:
                    seen.add(len(ids))
                    rag.engine.serve([Request(prompt_ids=ids,
                                              max_new_tokens=2)])
        for log in (self.t_retrieve, self.t_serve, self.t_maintain,
                    self.served):
            log.clear()

    def call(self, i: int) -> None:
        with self.ctx.annotate("answer"):
            self.rag.answer(self.schedule.queries[i],
                            max_new_tokens=int(self.schedule.max_new[i]))

    def window(self, seconds: float):
        win = super().window(seconds)
        n = win.attempted
        from counts import answer_flops
        m = self.ctx.config["model"]
        self.requests = [(list(r.prompt_ids), list(r.out_ids))
                         for r in self.served[:n]]
        win.stats.update(
            answer_retrieve_s=float(np.median(self.t_retrieve[:n])),
            answer_serve_s=float(np.median(self.t_serve[:n])),
            answer_maintain_s=float(np.median(self.t_maintain[:n])),
            answer_flops=float(sum(answer_flops(m, len(p), len(o))
                                   for p, o in self.requests)))
        win.notes.append(
            f"served tokens {sum(len(o) for _, o in self.requests)}, "
            f"prompt tokens {sum(len(p) for p, _ in self.requests)}")
        return win

    def verify_more(self):
        """Greedy tokens against the float32 reference: at each served
        position, how far the served token's logit lies below the
        reference's best; the widest gap over a sample drawn from the
        seed that holds the longest answer and at least
        ``check_served_tokens`` tokens.

        With the ``fp8_control`` fault the reference computed in float8
        takes the program's place: at the same positions of the same
        prompts and served tokens, the gap of the token that float8 puts
        first."""
        import jax.numpy as jnp
        from reference import qwen2
        if not self.requests:
            return [Check("logit_gap", float("inf"), self.logit_gap_limit())]
        rng = np.random.default_rng([self.ctx.seed, 3])
        n = len(self.requests)
        longest = max(range(n), key=lambda i: len(self.requests[i][1]))
        order = [longest] + [int(i) for i in rng.permutation(n)
                             if i != longest]
        want, pick, got = self.ctx.traffic["check_served_tokens"], [], 0
        for i in order:
            if got >= want:
                break
            pick.append(i)
            got += len(self.requests[i][1])
        m = self.ctx.config["model"]
        cfg = (m["num_attention_heads"], m["num_key_value_heads"],
               m["hidden_size"] // m["num_attention_heads"],
               float(m["rope_theta"]), float(m["rms_norm_eps"]),
               m["vocab_size"])
        cache = self.ctx.config["serving"]["cache_size"]
        control = self.ctx.fault == "fp8_control"
        widest = 0.0
        for i in pick:
            prompt, out = self.requests[i]
            seq = prompt + out[:-1]
            toks = np.zeros(cache, np.int32)
            toks[:len(seq)] = seq
            pos = np.arange(len(prompt) - 1, len(seq))
            logits = qwen2.logits(self.weights, jnp.asarray(toks), cfg=cfg)
            served = jnp.asarray(out, np.int32)
            if control:
                low = qwen2.logits(self.weights, jnp.asarray(toks), cfg=cfg,
                                   mode="fp8")
                served = jnp.argmax(low[pos], axis=-1).astype(jnp.int32)
            gap = np.asarray(qwen2.served_gap(logits[pos], served))
            widest = max(widest, float(gap.max()))
        self.win.notes.append(f"generator check: {len(pick)} answers, "
                              f"{got} served tokens")
        return [Check("logit_gap", widest, self.logit_gap_limit())]

    def logit_gap_limit(self) -> float:
        return float(self.ctx.config["checks"]["logit_gap"])
