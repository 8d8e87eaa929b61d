"""The whole serving step's share of the chip's bf16 peak: the dense
operations of every prompt and output token the window processed
(latent attention at the cheaper form of each phase, the head where a
token is sampled) plus the routed experts' operations of the pairs the
program's ``expert_pairs`` counter saw in the window, over window x
peak (``roofline/latent_moe.py``). A decode token stamped in the window
counts at its own position; a prompt counts whole when its first token
was stamped in the window. Silent without the routing counters."""

import os

from perfbench.harness.cells import load_module
from perfbench.roofline import latent_moe, peaks

_routing = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "_routing.py")
)


def read(ctx):
    cfg = ctx["config"]
    t0, t1 = ctx["window"]
    calls = _routing.routed_calls(ctx, t0, t1)
    if not calls:
        return None
    need = latent_moe.routed_flops(cfg, sum(p for p, _, _ in calls))
    for r in ctx["requests"]:
        P = r["prompt_len"]
        for i, t in enumerate(r["stamps"]):
            if not t0 <= t < t1:
                continue
            if i == 0:
                need += latent_moe.prompt_flops(cfg, P)
            else:
                need += latent_moe.decode_token_flops(cfg, P + i)
    peak = peaks.peaks(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * need / (t1 - t0) / (ctx["cell"].chips * peak)
