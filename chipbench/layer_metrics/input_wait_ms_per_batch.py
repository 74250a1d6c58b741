"""Host wall milliseconds the loop waited in the loader's ``next`` per
batch, over the whole window. Source: the benchmark's host span around it.
A job that takes no batch from a loader has no such span and no number."""


def read(ctx):
    if "input_wait" not in ctx.spans.seconds or not ctx.window.batches:
        return None
    return 1e3 * ctx.spans.total("input_wait") / ctx.window.batches
