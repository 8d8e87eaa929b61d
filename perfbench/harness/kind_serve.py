"""A serving cell: ``ServeEngine.submit`` / ``step`` under offered load.

The benchmark's own copy of ``serve/loadgen.drive``: requests are
submitted when they are DUE, every token is stamped through
``RequestHandle.on_token``, latencies run from the due time, and the
generator's lateness is reported. Set-up makes the weights on the device
from the seed, walks every prefill bucket with one prompt near
``max_len``, compiles every decode bucket, and runs the mix's ramp; then
the window measures for ``--seconds``. The comparison runs once the
window has closed, the peak has been read and the engine is freed.
"""

import gc
import time

from perfbench.harness import check as check_mod
from perfbench.harness import device as device_mod
from perfbench.harness import result
from perfbench.harness import trace as trace_mod
from perfbench.harness import traffic as traffic_mod
from perfbench.harness import weights as W

clock = time.perf_counter


class Rec:
    """One request as the benchmark saw it (times on ``perf_counter``)."""

    __slots__ = ("index", "due", "submitted", "stamps", "handle",
                 "prompt_ids", "max_new", "prefix")

    def __init__(self, index, due, spec):
        self.index, self.due = index, due
        self.submitted = None
        self.stamps = []
        self.handle = None
        self.prompt_ids = spec["prompt_ids"]
        self.max_new = spec["max_new_tokens"]
        self.prefix = spec["prefix"]

    def on_token(self, handle, token):
        self.stamps.append(clock())

    @property
    def completed(self):
        h = self.handle
        return h is not None and h.status.value == "completed"


class SeedWeights:
    """The seed's weights for the reference, made leaf group by leaf
    group on demand (a 7.5 GB model is never held whole)."""

    def __init__(self, seed, fam, cfg):
        import jax

        self.key = W.seed_key(seed)
        self.fam, self.cfg = fam, cfg
        self.dtype = fam.param_dtype(cfg)
        self.num_layers = fam.num_layers(cfg)
        top, layer = fam.top_spec(cfg), fam.layer_spec(cfg)
        self._top = jax.jit(lambda k: W.make_top(k, top, self.dtype))
        self._layer = jax.jit(
            lambda k, l: W.make_layer(k, l, layer, self.dtype)
        )
        self._stacked = jax.jit(
            lambda k: W.make_stacked(k, self.num_layers, layer, self.dtype)
        )
        self._cache = {}

    def top(self):
        if "top" not in self._cache:
            self._cache["top"] = self._top(self.key)
        return self._cache["top"]

    def layer(self, l):
        return self._layer(self.key, l)

    def stacked(self):
        if "stacked" not in self._cache:
            self._cache["stacked"] = self._stacked(self.key)
        return self._cache["stacked"]


def build_engine(run):
    import jax
    import numpy as np

    from pytorch_distributed_tpu.ops.paged_attention import (
        resolve_paged_attention_impl, set_paged_attention_impl,
    )
    from pytorch_distributed_tpu.serve import EngineConfig, ServeEngine
    from pytorch_distributed_tpu.serve.scheduler import Request
    from pytorch_distributed_tpu.serve.telemetry import ServeTelemetry

    cell = run.cell
    cfg, fam, es = run.config(), cell.family(), run.setting("engine")
    if run.rehearse:
        # the rehearsal runs the kernel too, interpreted ("auto" would
        # pick the gather impl off-TPU and rehearse another program)
        set_paged_attention_impl("kernel")
    if resolve_paged_attention_impl() != "kernel":
        raise RuntimeError("the cells time the paged-attention kernel")
    model = fam.build_model(cfg)
    sw = SeedWeights(run.seed, fam, cfg)
    # one jitted call, on the device, in the type they are served in
    params = jax.jit(lambda k: W.program_params(k, fam, cfg))(sw.key)
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=es["num_slots"], max_len=es["max_len"],
        prefill_chunk=es["prefill_chunk"], page_size=es["page_size"],
        num_pages=es.get("num_pages"), prefix_cache=es["prefix_cache"],
    ), clock=clock)
    # warm-up: one prompt near max_len walks every prefill bucket and
    # reaches a decode tick; then every decode bucket is compiled
    warm = np.ones(es["max_len"] - 2, np.int32)
    h = engine.submit(Request(warm, max_new_tokens=2))
    engine.run_until_drained()
    if h.status.value != "completed" or engine.decode_compiles < 1:
        raise RuntimeError(f"warm-up request: {h.status.value}")
    engine.precompile_decode_buckets()
    engine.telemetry = ServeTelemetry(
        writer=engine.telemetry.writer, clock=engine.telemetry.clock,
        engine_id=engine.telemetry.engine_id,
    )
    return engine, Request


def drive(engine, Request, recs, *, mix, seconds, trace_plan, want_steps):
    """Offer the load and step the engine; returns the window and what
    was seen. Times are ``perf_counter`` seconds."""
    import jax

    n = len(recs)
    ramp = mix.get("ramp", {})
    backlog = mix["arrivals"]["process"] == "backlog"
    steps = []            # (start, end, did) of every engine.step()
    counters = {}
    traced = None
    tracing_on = False
    t_trace = mark = None
    t_start = clock()
    w0 = None if backlog else t_start + ramp.get("seconds", 0.0)
    opened = False
    i = done_count = 0
    slots = engine.config.num_slots

    def snapshot():
        pool = engine.pool
        return {
            "decode_compiles": engine.decode_compiles,
            "prefill_compiles": engine.prefill_compiles,
            "prefix_lookups": pool.prefix_lookups,
            "prefix_hits": pool.prefix_hits,
            "shared_tokens": pool.shared_tokens,
            "prompt_tokens": pool.prompt_tokens,
        }

    while True:
        now = clock()
        if w0 is not None and not opened and now >= w0:
            opened = True
            counters["open"] = snapshot()
        if opened and now >= w0 + seconds:
            break
        if (trace_plan and opened and not tracing_on and t_trace is None
                and now - w0 >= trace_plan[0]):
            trace_mod.start(trace_plan[1])
            with jax.profiler.TraceAnnotation(trace_mod.MARK):
                mark = clock()
            t_trace, tracing_on = mark, True
        while i < n and now - t_start >= recs[i].due:
            r = recs[i]
            r.handle = engine.submit(Request(
                r.prompt_ids, max_new_tokens=r.max_new, temperature=0.0,
                seed=r.index,
            ))
            r.handle.on_token = r.on_token
            r.submitted = clock()
            i += 1
        a = clock()
        did = engine.step()
        if want_steps:
            steps.append((a, clock(), did))
        if backlog and w0 is None:
            # the ramp is set-up: every slot occupied and the first
            # requests completed
            done_count = sum(1 for r in recs[:i] if r.completed)
            if (done_count >= ramp.get("until_completed", 1)
                    and engine.pool.num_occupied >= slots):
                w0 = clock()
        if not did and i < n:
            wait = recs[i].due - (clock() - t_start)
            if wait > 0:
                time.sleep(min(wait, 0.0005))
        elif not did and i >= n and not engine.has_work():
            break  # nothing left to offer: the mix was too small
    w1 = clock()
    counters["close"] = snapshot()
    if tracing_on:
        # the traced stretch ends with the window; stopping the profiler
        # stalls the host, so it waits until here
        jax.block_until_ready(engine.pool.cache)
        t_stop = clock()
        trace_mod.stop()
        traced = (mark, t_trace, t_stop)
    return {
        "t_start": t_start, "w0": w0, "w1": w1, "submitted": i,
        "steps": steps, "counters": counters, "traced": traced,
    }


def drain(engine, recs, seen, *, limit_s):
    """Once the window has closed: no new arrivals; wait until every
    request that was due in the window has its first token (late is
    late, not wrong: its time to first token counts the wait)."""
    t_start, w0, w1 = seen["t_start"], seen["w0"], seen["w1"]
    due_in = [
        r for r in recs[: seen["submitted"]]
        if w0 <= t_start + r.due < w1
    ]
    deadline = clock() + limit_s
    while clock() < deadline and any(
        not r.stamps and not r.handle.done for r in due_in
    ):
        if not engine.step():
            time.sleep(0.0005)
    return due_in


def run_cell(run):
    import numpy as np

    import pytorch_distributed_tpu as ptd
    from pytorch_distributed_tpu.runtime import precision, tracing

    cell = run.cell
    print(f"compile cache: {ptd.enable_compilation_cache()}", flush=True)
    cfg, fam = run.config(), cell.family()
    mix = run.traffic()
    open_loop = mix["arrivals"]["process"] != "backlog"
    ramp_s = mix.get("ramp", {}).get("seconds", 0.0)
    reqs, due = traffic_mod.generate(
        mix, run.seed, cfg["vocab_size"],
        horizon_s=ramp_s + run.seconds + 1.0,
    )
    recs = [Rec(i, d, r) for i, (r, d) in enumerate(zip(reqs, due))]
    notes = [traffic_mod.describe(mix, reqs, due)]
    trace_plan = None
    if run.trace:
        length = min(run.setting("engine").get("trace_seconds", 3.0),
                     run.seconds / 2)
        trace_plan = (run.seconds - length, run.out_dir())
    tracer = tracing.configure(None, max_events=2_000_000) \
        if run.trace else None
    with precision.use_policy(run.policy()):
        engine, Request = build_engine(run)
        hook = run.overrides.get("after_build")
        if hook:
            hook(engine)
        seen = drive(engine, Request, recs, mix=mix, seconds=run.seconds,
                     trace_plan=trace_plan, want_steps=run.trace)
        if seen["w0"] is None:
            raise RuntimeError("the ramp never ended: the mix is too "
                               "small for this engine")
        t_start, w0, w1 = seen["t_start"], seen["w0"], seen["w1"]
        if open_loop:
            judged = drain(engine, recs, seen,
                           limit_s=mix.get("drain_limit_s", 60.0))
        else:
            judged = [r for r in recs[: seen["submitted"]]
                      if r.stamps and r.handle.done
                      and w0 <= r.stamps[-1] < w1]
    spans = trace_mod.host_spans(tracer) if tracer else []
    tracing.clear()
    peak = device_mod.memory_peak_bytes(run.devices, cell.chips)
    window_s = w1 - w0
    in_window = lambda t: w0 <= t < w1  # noqa: E731
    sent = recs[: seen["submitted"]]
    tokens_in_window = sum(
        1 for r in sent for t in r.stamps if in_window(t)
    )
    e2e = {"setup_s": w0 - run.t_process}
    # a request that ended otherwise than completed, or never got a
    # first token though the benchmark waited, has failed
    failed = [r for r in judged
              if (r.handle.done and not r.completed) or not r.stamps]
    late = [r.submitted - (t_start + r.due) for r in sent]
    if open_loop:
        miss = mix.get("drain_limit_s", 60.0) * 1e3
        ttft = [
            (r.stamps[0] - (t_start + r.due)) * 1e3 if r.stamps else miss
            for r in judged
        ]
        # every gap between two consecutive tokens of one request
        # whose later token fell inside the window, of all requests
        gaps = [
            (b - a) * 1e3 for r in sent
            for a, b in zip(r.stamps, r.stamps[1:]) if in_window(b)
        ]
        e2e["ttft_p95_ms"] = result.percentile(ttft, 95)
        e2e["itl_p95_ms"] = result.percentile(gaps, 95)
        notes.append(
            f"latency: ttft p50/p95 {result.percentile(ttft, 50)}/"
            f"{e2e['ttft_p95_ms']} ms over {len(ttft)} requests; "
            f"itl p50/p95 {result.percentile(gaps, 50)}/"
            f"{e2e['itl_p95_ms']} ms over {len(gaps)} gaps"
        )
    e2e["serve_tokens_per_s"] = tokens_in_window / window_s
    notes.append(
        f"load: due {sum(1 for r in recs if t_start + r.due < w1)} sent "
        f"{len(sent)} completed {sum(r.completed for r in sent)} judged "
        f"{len(judged)} failed {len(failed)}; generator late p50/p95/max "
        f"{result.percentile(late, 50) * 1e3:.3f}/"
        f"{result.percentile(late, 95) * 1e3:.3f}/{max(late) * 1e3:.3f} ms;"
        f" window {window_s:.3f}s, {tokens_in_window} tokens in it "
        f"({tokens_in_window / window_s:.1f}/s); ramp {w0 - t_start:.2f}s"
    )
    c0, c1 = seen["counters"].get("open"), seen["counters"]["close"]
    if c0 is None:  # a backlog's window opens inside the loop's body
        c0 = c1
    compiles = (c1["decode_compiles"] - c0["decode_compiles"]
                + c1["prefill_compiles"] - c0["prefill_compiles"])
    finished = [r for r in sent if r.completed]
    short = sum(1 for r in finished if len(r.handle.tokens) != r.max_new)
    es = run.setting("engine")
    ctx = {
        "cell": cell, "run": run, "end_to_end": e2e, "spans": spans,
        "window": (w0, w1), "t_start": t_start, "config": cfg,
        "steps": seen["steps"], "counters": (c0, c1),
        "num_slots": es["num_slots"], "page_size": es["page_size"],
        "requests": [
            {"due": t_start + r.due, "submitted": r.submitted,
             "stamps": r.stamps, "prompt_len": len(r.prompt_ids),
             "max_new": r.max_new, "prefix": r.prefix,
             "id": r.handle.request.request_id, "completed": r.completed}
            for r in sent
        ],
        "ttft_miss_ms": mix.get("drain_limit_s", 60.0) * 1e3,
        "shared_prefix_tokens": (mix.get("shared_prefix") or {}).get(
            "tokens", 0),
        "device_kind": run.devices[0].device_kind,
    }
    chk = run.setting("check")
    sample = _sample(finished, run.seed, chk.get("sample_requests", 4))
    served = [(np.asarray(r.prompt_ids), list(r.handle.tokens))
              for r in sample]
    # the program's state is no longer needed: free it for the reference
    n_judged = len(judged)
    del engine, finished, sample, judged, sent
    for r in recs:
        r.handle = None
    gc.collect()
    t_ref = clock()
    readings = served_readings(
        run, served, with_control=run.overrides.get("with_control", False)
    )
    notes.append(
        f"reference: {len(served)} requests, "
        f"{sum(len(t) for _, t in served)} served tokens, longest "
        f"{max(len(p) + len(t) for p, t in served)} positions, in "
        f"{clock() - t_ref:.1f}s; widest gap at {readings['where']}"
    )
    ctx["control_reading"] = readings.get("control_gap")
    values = {
        "served_token_gap": readings["gap"],
        "compiles_in_window": float(compiles),
        "token_count_mismatch": float(short),
    }
    checks = [
        result.Check(name, values[name], float(limit))
        for name, limit in chk["limits"].items()
    ]
    if run.trace and seen["traced"] and not run.rehearse:
        ctx["trace"] = trace_mod.reduce_run(run, seen["traced"], spans)
    else:
        ctx["trace"] = None
    return result.Outcome(
        end_to_end=e2e, attempted=n_judged, failed=len(failed),
        checks=checks,
        memory_peak_bytes=peak, context=ctx, notes=notes,
    )


def _sample(finished, seed, k):
    """``k`` finished requests drawn from the seed, the longest among
    them."""
    import numpy as np

    if not finished:
        raise RuntimeError("no request finished: nothing to compare")
    longest = max(finished, key=lambda r: len(r.prompt_ids) + r.max_new)
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng(seed + 1)
    picks = rng.permutation(len(rest))[: max(k - 1, 0)]
    return [longest] + [rest[i] for i in picks]


def served_readings(run, served, *, with_control=False):
    """The widest gap by which a served token's logit lies below the
    reference's best, over every served token of the sample; and, for
    the control, the same of the token the lower precision puts first."""
    import jax
    import numpy as np

    cell = run.cell
    cfg, fam = run.config(), cell.family()
    ref = cell.reference()
    sw = SeedWeights(run.seed, fam, cfg)
    worst, where, control = 0.0, None, 0.0
    with jax.default_matmul_precision("highest"):
        for n, (prompt, tokens) in enumerate(served):
            ids = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
            start = len(prompt) - 1
            logits = np.asarray(ref.served_logits(cfg, sw, ids, start))
            gaps = check_mod.token_gaps(logits, tokens)
            j = int(gaps.argmax())
            if gaps[j] >= worst:
                worst, where = float(gaps[j]), (
                    f"request {n} (prompt {len(prompt)}), token {j} of "
                    f"{len(tokens)}"
                )
            if with_control:
                low = np.asarray(
                    ref.served_logits(cfg, sw, ids, start, "fp8")
                )
                first = low.argmax(axis=-1)
                control = max(control, float(
                    check_mod.token_gaps(logits, first).max()
                ))
    out = {"gap": worst, "where": where}
    if with_control:
        out["control_gap"] = control
    return out


def control(run, what):
    """A short run of the cell whose served tokens are read twice: the
    program's own gap, and the gap of the token the fp8 reference puts
    first at the same positions."""
    if what != "control":
        raise ValueError(f"unknown control {what!r}")
    run.overrides = dict(run.overrides, with_control=True)
    outcome = run_cell(run)
    out = {c.name: c.value for c in outcome.checks}
    out["control_gap"] = outcome.context["control_reading"]
    out.update(outcome.end_to_end)
    return out


run = run_cell  # the name every kind gives its entry
