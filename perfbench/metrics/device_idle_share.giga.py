"""1 - busy/window of the traced stretch of the window, from the device
trace (union of the intervals in which an operation ran)."""

from perfbench.harness import trace


def read(ctx):
    return trace.idle_share(ctx.get("trace"))
