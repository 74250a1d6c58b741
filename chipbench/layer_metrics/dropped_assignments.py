"""Assignments to a held expert that no grouped matmul computed, summed
over the expert layers and over every step of the window: 0, since the
buffer covers the worst case. Source: the step's own counters
(``jobs/trainstep_counted.py``); None from a job that keeps none."""


def read(ctx):
    return ctx.counters.get("dropped_assignments")
