"""Host milliseconds per batch inside the facade's own spans and NOT inside
a span nested in them: ``graft/facade.model|loss|backward|step|
detach_and_sync_loss|fused_step`` (and ``step.flush_micros``,
``step.materialize_lazies``), duration less children. Every dispatch of a
compiled program is inside a child span, so this is the facade's Python and
not the time it is blocked on a full dispatch queue (which
``facade_host_ms_per_batch`` includes). Source: the program's spans in the
profile of the traced steps (``program_trace``)."""

from chipbench import program_trace


def read(ctx):
    found = program_trace.analysis(ctx)
    spans = found and found["spans"]
    if not spans or not spans["facade_batches"]:
        return None
    return 1e3 * spans["facade_self_s"] / spans["facade_batches"]
