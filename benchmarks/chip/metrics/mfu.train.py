"""Model FLOP utilisation of training: the least operations per token
(``flops.train_flops_per_token``, recomputation not counted) times the
tokens trained per second over the traced part of the window (host clock),
over chips times the chip's peak, in percent."""
from benchmarks.chip.metrics._common import traced


def read(ctx):
    steps, wall = traced(ctx, "steps"), traced(ctx, "t")
    rec = ctx["record"]
    if not steps or not wall:
        return None
    tokens = steps * rec["tokens"] / rec["steps"]
    return 100.0 * rec["flops_per_token"] * tokens / (
        wall * ctx["chips"] * ctx["peak"]["flops"])
