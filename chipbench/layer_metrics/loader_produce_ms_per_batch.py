"""Milliseconds the loader's feeder thread spends producing one batch:
``graft/loader.collect`` (blocked in the workers' futures) plus
``graft/loader.collate`` (stacking, under the GIL the dispatch thread also
needs). The workers' own fetch seconds (``worker_s``, an argument of the
spans) are printed beside it by ``program_trace``. Source: the program's
spans in the profile of the traced steps."""

from chipbench import program_trace


def read(ctx):
    found = program_trace.analysis(ctx)
    spans = found and found["spans"]
    if not spans or not spans["loader_batches"]:
        return None
    return 1e3 * spans["loader_produce_s"] / spans["loader_batches"]
