"""Share of the window's wall seconds above its median pace: what the
throughput metric, taken at the window's median pace (``loop.pace``),
leaves out. Steps that are slow now and then (the host descheduled by a
neighbour, a periodic save or sync, the profiler starting in this traced
run) show here and not in the rate; a change that slows every step shows
in the rate. Source: the host's clock, whole window."""


def read(ctx):
    window = ctx.window
    if not window.step_s > 0 or not window.seconds > 0:
        return None
    return 100.0 * window.stall_s / window.seconds
