"""Shared by the readers of the expert layers' routing counters: the
``expert_pairs`` / ``experts_hit`` / ``expert_peak`` args (one entry an
expert layer) on ``serve.decode_tick`` and ``serve.prefill_chunk``
spans. A program without the counters gives every reader ``None``."""

SPANS = ("serve.decode_tick", "serve.prefill_chunk")


def routed_calls(ctx, t0, t1):
    """``[(pairs, hit, peak)]``, one an expert layer a program call, of
    the spans that started in ``[t0, t1)``."""
    out = []
    for name, a, _, args in ctx["spans"]:
        if name in SPANS and t0 <= a < t1 and "expert_pairs" in args:
            out += list(zip(args["expert_pairs"], args["experts_hit"],
                            args["expert_peak"]))
    return out
