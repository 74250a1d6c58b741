"""Share of its roofline the sparse attention core reaches: the least time
the chip could take for the pairs the selection hands the core (two matmuls
of every query head forward, four backward, ``families/keye_vl2.
sparse_attention_cost``; every forward the trace shows ran) over the seconds
of the core's kernels, ``sparse_core_fwd``, ``sparse_core_dq`` and
``sparse_core_dkv``, in the device trace (``families/keye_vl2.
kernel_seconds``, by the kernels' own names; the job hands the family's
``roofline`` on as the counter ``sparse_roofline``). The tiles those kernels
run past the selection and the KL's gradient made inside the backward are
in the seconds and not in the work: the share reads what a sparser core
would gain. None where there is no such counter or the trace shows no such
kernel."""


def read(ctx):
    roofline = ctx.counters.get("sparse_roofline")
    return None if roofline is None else roofline(
        ctx.device_kind, "sparse_attention"
    )
