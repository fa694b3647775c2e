"""mfu: model FLOPs of the prompt and output tokens the window processed,
over the window's seconds times the chips' peak bf16 FLOP/s (%).

FLOPs are counted from shapes at each token's real context length by the
configuration's family (``span_flops`` and ``head_flops`` of
``bench/families/<family>.py``): linears at their structured cost, the
vocabulary head once per sampled token; padding never counts."""


def read(run):
    if run.peak is None:
        return None
    w = run.win
    flops = sum(t.flops for t in run.rec.ticks if w.open < t.end <= w.close)
    peak = run.peak["bf16_flops_per_s"] * len(run.devices)
    return 100.0 * flops / ((w.close - w.open) * peak)
