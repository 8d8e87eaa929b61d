"""The grouped expert product's share of its roofline in the traced
stretch: the least time the chip could take for what the ticks and
chunks of the stretch routed here — per expert layer and program call,
max(operations/peak, bytes/bandwidth) with the operations of the
``expert_pairs`` counted and the bytes of the weights of the
``experts_hit`` plus the activations — over the summed device time of
the ``expert_gmm`` operations."""

import os

from perfbench.harness import trace as trace_mod
from perfbench.harness.cells import load_module
from perfbench.roofline import latent_moe, peaks

_routing = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "_routing.py")
)
KERNEL = "expert_gmm"


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    secs, names = trace_mod.kernel_seconds(tr["ops"], KERNEL)
    a, b = tr["span"]
    calls = _routing.routed_calls(ctx, a, b)
    if secs <= 0 or not calls:
        return None
    pk = peaks.peaks(ctx["device_kind"])
    least = 0.0
    for pairs, hit, _ in calls:
        ops, nbytes = latent_moe.expert_gmm_call(ctx["config"], pairs, hit)
        least += max(ops / pk["bf16_flops_per_s"],
                     nbytes / pk["hbm_bytes_per_s"])
    print(f"expert_gmm_roofline: {len(calls)} layer-calls, kernel "
          f"{secs:.4f}s over {names} names, least {least:.4f}s", flush=True)
    return 100.0 * least / secs
