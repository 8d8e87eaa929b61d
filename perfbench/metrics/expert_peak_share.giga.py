"""Imbalance of the routing onto the experts held here: the most pairs
any one held expert got in a call over the mean a held expert got
(``expert_peak`` over ``expert_pairs`` / experts held), averaged over the
window's expert-layer calls that routed any pair here; 100 is an even
spread."""

import os

from perfbench.harness.cells import load_module

_routing = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "_routing.py")
)


def read(ctx):
    held = ctx["config"]["n_routed_experts"]
    shares = [
        100.0 * peak * held / pairs
        for pairs, _, peak in _routing.routed_calls(ctx, *ctx["window"])
        if pairs
    ]
    return sum(shares) / len(shares) if shares else None
