"""How late the load generator ran: 95th percentile, over the requests
due in the window, of submit time minus due time. A starved generator
must not be read as a fast server."""

from perfbench.harness.result import percentile


def read(ctx):
    t0, t1 = ctx["window"]
    late = [
        (r["submitted"] - r["due"]) * 1e3 for r in ctx["requests"]
        if t0 <= r["due"] < t1
    ]
    return percentile(late, 95)
