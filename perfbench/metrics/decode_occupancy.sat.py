"""Mean number of decoding rows a tick carried (``active`` of the
``serve.decode_tick`` spans in the window) over the engine's slots."""


def read(ctx):
    t0, t1 = ctx["window"]
    active = [
        args["active"] for name, a, _, args in ctx["spans"]
        if name == "serve.decode_tick" and t0 <= a < t1 and "active" in args
    ]
    if not active:
        return None
    return 100.0 * sum(active) / len(active) / ctx["num_slots"]
