"""Time to first token, 95th percentile over all requests due in the
window, from the time each was DUE (a request with no first token a
minute after the close counts at that minute). Not an end-to-end metric
in this cell: a request's first token rides whole engine steps, and at
a 215 ms step the tail of ~57 requests moves by a step's phase (sets of
six runs spread 2.3% to 7.6%, my chip runs, PR 24), which no bound of at
most 10% can hold."""

from perfbench.harness.result import percentile


def read(ctx):
    t0, t1 = ctx["window"]
    miss = ctx["ttft_miss_ms"]
    ttft = [
        (r["stamps"][0] - r["due"]) * 1e3 if r["stamps"] else miss
        for r in ctx["requests"] if t0 <= r["due"] < t1
    ]
    return percentile(ttft, 95)
