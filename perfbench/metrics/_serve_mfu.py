"""Shared by the ``mfu.serve_*`` readers: forward operations of every
prompt and output token the window processed, over window x peak.

A decode token stamped in the window counts at its own position. A
prompt counts whole when its first token was stamped in the window
(its chunks ran just before); the positions a shared prefix served from
cached pages are taken off by the pool's hit counter."""

from perfbench.roofline import flops, peaks


def serve_mfu(ctx):
    cfg = ctx["config"]
    t0, t1 = ctx["window"]
    need = 0
    for r in ctx["requests"]:
        P = r["prompt_len"]
        for i, t in enumerate(r["stamps"]):
            if not t0 <= t < t1:
                continue
            if i == 0:
                need += flops.forward_flops_span(cfg, 0, P)
            else:
                need += flops.forward_flops_span(cfg, P + i - 1, P + i)
    c0, c1 = ctx["counters"]
    shared = ctx["shared_prefix_tokens"]
    if shared:
        hits = (c1["shared_tokens"] - c0["shared_tokens"]) // shared
        need -= hits * flops.forward_flops_span(cfg, 0, shared)
    peak = peaks.peaks(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * need / (t1 - t0) / (ctx["cell"].chips * peak)
