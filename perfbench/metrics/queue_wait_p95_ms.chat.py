"""From due to admitted: 95th percentile, over the requests due in the
window, of the ``serve.admit`` span's start minus the due time."""

from perfbench.harness.result import percentile


def read(ctx):
    t0, t1 = ctx["window"]
    admitted = {
        args.get("request"): a for name, a, _, args in ctx["spans"]
        if name == "serve.admit"
    }
    waits = [
        (admitted[r["id"]] - r["due"]) * 1e3 for r in ctx["requests"]
        if t0 <= r["due"] < t1 and r["id"] in admitted
    ]
    return percentile(waits, 95)
