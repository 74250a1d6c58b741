"""Model FLOP/s utilization: the FLOPs the forward and backward passes of
one optimizer step require (the family's arithmetic; recomputation not
counted) over the seconds an optimizer step at the median pace of this
traced run's whole window (the pace the throughput metric is taken at), on
the host's clock, over chips x the bf16 peak of ``peaks.json``.
(Not from the trace's step period: on the trace's device timeline a step
reads 3-5% shorter than on the host's clock, PERF.md PR 22, and users live
on the host's.)"""

from chipbench.peaks import mfu_pct


def read(ctx):
    if ctx.device_kind is None or not ctx.window.step_s > 0:
        return None
    rate = ctx.counters["flops_per_step"] / ctx.window.step_s
    return mfu_pct(rate, ctx.device_kind, ctx.chips)
