"""Median time from one ``train.step`` span's start to the next inside
the window: the step's period whatever the loop's sync cadence (the span
itself covers only the dispatch)."""

from perfbench.harness.result import median


def read(ctx):
    t0, t1 = ctx["window"]
    starts = sorted(
        a for name, a, _, _ in ctx["spans"]
        if name == "train.step" and t0 <= a <= t1
    )
    periods = [(b - a) * 1e3 for a, b in zip(starts, starts[1:])]
    return median(periods)
