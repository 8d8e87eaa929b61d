"""The host's own time in one ``engine.step()`` that ran a decode tick:
the step's wall time (stamped by the benchmark around the call) minus
the ``serve.token_fetch`` spans inside it, which wait for the device.
Median over the window's steps. A final prefill chunk's token read is a
device wait outside any span and stays in; the median is of steps
without one."""

import bisect

from perfbench.harness.result import median


def read(ctx):
    t0, t1 = ctx["window"]
    fetch = sorted(
        (a, b) for name, a, b, _ in ctx["spans"]
        if name == "serve.token_fetch"
    )
    starts = [a for a, _ in fetch]
    ticks = sorted(
        a for name, a, _, _ in ctx["spans"] if name == "serve.decode_tick"
    )
    out = []
    for a, b, did in ctx["steps"]:
        if not did or a < t0 or b > t1:
            continue
        i = bisect.bisect_left(ticks, a)
        if i >= len(ticks) or ticks[i] > b:
            continue  # no decode tick in this step
        waited = 0.0
        j = bisect.bisect_left(starts, a)
        while j < len(fetch) and fetch[j][0] < b:
            waited += fetch[j][1] - fetch[j][0]
            j += 1
        out.append((b - a - waited) * 1e3)
    return median(out)
