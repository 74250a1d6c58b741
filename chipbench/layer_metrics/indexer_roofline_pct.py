"""Share of its roofline the indexer's selection kernel reaches: the least
time the chip could take for the scores of every causal pair (``HI x DI x
2`` FLOPs a pair) and the kernel's bytes, as many passes as the trace shows
the kernel ran (``families/keye_vl2.indexer_cost``), over the seconds of the
``sparse_select`` kernels in the device trace (``families/keye_vl2.
kernel_seconds``, by the kernel's own name; the job hands the family's
``roofline`` on as the counter ``sparse_roofline``). The bisection's
compares are the kernel's own affair. None where there is no such counter
or the trace shows no such kernel (a run without a trace, a program without
the indexer)."""


def read(ctx):
    roofline = ctx.counters.get("sparse_roofline")
    return None if roofline is None else roofline(ctx.device_kind, "indexer")
