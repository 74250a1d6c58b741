"""Rows of the fullest held expert over the mean rows of a held expert,
averaged over the expert layers and over the window's steps: 1.0 is an even
load. Source: the step's own counters (device scalars in its metrics, kept
by ``jobs/trainstep_counted.py``); None from a job that keeps none."""


def read(ctx):
    return ctx.counters.get("expert_load_max_over_mean")
