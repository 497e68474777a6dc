"""The generator's share of the chip's bf16 peak over the window: the
forward FLOPs of every answer's real prompt and served tokens
(``counts.answer_flops``; no padding rows or positions) over the window's
seconds times the peak."""


def read(r):
    flops = r.window.stats.get("answer_flops")
    if not flops:
        return None
    return 100.0 * flops / (r.window.seconds * r.peak["bf16_flops_per_s"])
