"""Host wall milliseconds inside the facade's calls (``model``, ``loss``,
``backward``, ``step``, ``detach_and_sync_loss``, or ``fused_step``) per
batch, over the whole window. Source: the benchmark's host spans."""


def read(ctx):
    names = [n for n in ctx.spans.seconds if n.startswith("facade.")]
    if not names or not ctx.window.batches:
        return None
    return 1e3 * ctx.spans.total(*names) / ctx.window.batches
