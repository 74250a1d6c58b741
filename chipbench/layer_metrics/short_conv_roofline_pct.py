"""Share of its roofline the short-convolution kernels reach: the least time
the chip could take for every conv layer's convolution in one optimizer step
(``families/lfm2_moe.short_conv_cost``: the bytes the forward and backward
kernels must read and write, each array once, every forward that the trace
shows ran) over the seconds of the ``custom-call``s under the ``short_conv``
scope in the device trace (``named_scopes``), at ``peaks.json``'s HBM
bandwidth (the bytes bound it: a few FLOPs an element). None without the
job's kernel costs or without kernel time under the scope (a run without a
trace, a program with no such kernel)."""

from chipbench import named_scopes
from chipbench.peaks import peaks_for


def read(ctx):
    costs_of = ctx.counters.get("kernel_costs")
    found = named_scopes.seconds(("short_conv",))
    if costs_of is None or not found or not found["steps"]:
        return None
    seconds = found["kernel_s"]["short_conv"] / found["steps"]
    if ctx.device_kind is None or not seconds > 0:
        return None
    # the convolution's cost does not depend on what the routing did
    costs = costs_of(0.0, 0.0)
    if "short_conv" not in costs:
        return None
    flops, nbytes = costs["short_conv"]
    peaks = peaks_for(ctx.device_kind)
    least = max(
        flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least / seconds
