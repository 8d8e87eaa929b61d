"""Shared by the readers of the serving loop's span tree: one
``serve.step`` span per ``engine.step()``, every span recorded during
it below it by ``span_id`` / ``parent_id`` (``runtime/tracing.py``).

All read ``ctx["spans"]`` (``(name, start_s, end_s, args)`` on the host's
``perf_counter`` clock) and ``ctx["window"]`` only. A step belongs to the
window when it starts and ends inside it. A program without the tree
(no ``serve.step`` span, no ids) gives every reader ``None``."""

from perfbench.harness.result import median, percentile

# the device waits inside a step: what is left of it is the host's own
WAITS = ("serve.token_fetch", "serve.first_token_fetch")


def step_trees(ctx):
    """``[(start_s, end_s, args, below)]`` of the window's steps in
    order of start; ``below`` maps a span name to the ``(start_s, end_s,
    args)`` of that step's descendants of that name."""
    t0, t1 = ctx["window"]
    by_id = {
        s[3]["span_id"]: s for s in ctx["spans"] if "span_id" in s[3]
    }
    trees = {
        i: (a, b, args, {}) for i, (name, a, b, args) in by_id.items()
        if name == "serve.step" and t0 <= a and b <= t1
    }
    for name, a, b, args in by_id.values():
        root = args["parent_id"]
        while root is not None and root not in trees:
            above = by_id.get(root)
            root = above[3]["parent_id"] if above else None
        if root is not None:
            trees[root][3].setdefault(name, []).append((a, b, args))
    return sorted(trees.values(), key=lambda t: t[0])


def _ms(a, b):
    return (b - a) * 1e3


def step_host_ms_p50(ctx):
    """Median, over the window's steps that ran a decode tick, of the
    step's duration minus the device waits below it."""
    return median([
        _ms(a, b) - sum(
            _ms(x, y) for name in WAITS for x, y, _ in below.get(name, ())
        )
        for a, b, args, below in step_trees(ctx) if args.get("decoded")
    ])


def _decode_only_ms(trees):
    return median([
        _ms(a, b) for a, b, args, _ in trees
        if args.get("decoded") and not args.get("prefill_chunks")
    ])


def decode_step_ms_p50(ctx):
    """Median duration of the window's steps that ran a decode tick and
    no prefill chunk: what token generation alone costs a step."""
    return _decode_only_ms(step_trees(ctx))


def prefill_chunk_ms_p50(ctx):
    """Median, over the window's steps with a prefill chunk, of what a
    chunk adds to the step: its duration, less the median decode-only
    step where it ran a decode tick too, over its chunks."""
    trees = step_trees(ctx)
    decode = _decode_only_ms(trees)
    out = []
    for a, b, args, _ in trees:
        chunks = args.get("prefill_chunks")
        if not chunks:
            continue
        if not args.get("decoded"):
            out.append(_ms(a, b) / chunks)
        elif decode is not None:
            out.append((_ms(a, b) - decode) / chunks)
    return median(out)


def kv_walk_useful_share(ctx):
    """Share of the paged kernel's grid steps (slots x the bucket's
    pages, every tick) that were over pages a live row's length
    reaches: ``live_pages`` over ``num_slots * n_pages``, summed over the
    decode ticks that started in the window."""
    t0, t1 = ctx["window"]
    live = walked = 0
    for name, a, _, args in ctx["spans"]:
        if (name in ("serve.decode_tick", "serve.spec_tick")
                and t0 <= a < t1 and "live_pages" in args):
            live += args["live_pages"]
            walked += ctx["num_slots"] * args["n_pages"]
    return 100.0 * live / walked if walked else None


def admit_wait_p95_ms(ctx):
    """From submitted to admitted: 95th percentile, over the requests
    due in the window, of the ``serve.admit`` span's start minus the end
    of the same request's ``serve.submit`` span."""
    t0, t1 = ctx["window"]
    submitted, admitted = {}, {}
    for name, a, b, args in ctx["spans"]:
        if name == "serve.submit":
            submitted[args.get("request")] = b
        elif name == "serve.admit":
            admitted[args.get("request")] = a
    return percentile([
        _ms(submitted[r["id"]], admitted[r["id"]]) for r in ctx["requests"]
        if t0 <= r["due"] < t1
        and r["id"] in submitted and r["id"] in admitted
    ], 95)
