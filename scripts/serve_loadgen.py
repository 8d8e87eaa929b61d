#!/usr/bin/env python
"""Offered-load generator for the continuous-batching serve engine.

Drives ``serve.ServeEngine`` with a seeded stream of requests at a fixed
arrival rate (uniform or Poisson), streams SLO telemetry through the
MetricsWriter JSONL protocol, and prints the run summary — the
command-line twin of bench.py's ``serving`` phase, for interactive
profiling and capacity probing::

    python scripts/serve_loadgen.py --model gpt2-tiny --requests 32 \\
        --rate 30 --slots 8 --prompt-len 4,16 --new-tokens 8,32 \\
        --temperature 0.8 --top-p 0.95 --log /tmp/serve.jsonl

``--rate 0`` submits everything up front (closed-loop saturation).
Params are randomly initialized — the workload numbers (tokens/sec,
TTFT percentiles, occupancy) measure the ENGINE, not any checkpoint.

Storm mode (r18) drives a FLEET behind the deterministic router::

    python scripts/serve_loadgen.py --engines 4 --router --requests 64
    python scripts/serve_loadgen.py --engines 4 --router --disagg \\
        --store --prefix-share 0.8 --requests 64

``--router`` load-balances N solo engines; ``--disagg`` splits them
into prefill/decode tiers with ring KV migration between them;
``--store`` shares one cross-engine prefix registry so a hot system
prompt is prefilled once per fleet. Arrivals stay seeded and
replayable — the same ``--seed`` routes the same storm identically —
and the summary reports p50/p95/p99 TTFT, aggregate tokens/s,
migration bytes, and replay counts.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def build_model(name: str):
    if name == "gpt2-tiny":
        from pytorch_distributed_tpu.models.gpt2 import (
            GPT2Config, GPT2LMHead,
        )
        return GPT2LMHead(GPT2Config.tiny())
    if name == "gpt2-small":
        from pytorch_distributed_tpu.models.gpt2 import (
            GPT2Config, GPT2LMHead,
        )
        return GPT2LMHead(GPT2Config.small())
    if name == "llama-tiny":
        from pytorch_distributed_tpu.models.llama import (
            LlamaConfig, LlamaForCausalLM,
        )
        return LlamaForCausalLM(LlamaConfig.tiny())
    if name == "qwen2-tiny":
        from pytorch_distributed_tpu.models.qwen2 import (
            Qwen2Config, Qwen2ForCausalLM,
        )
        return Qwen2ForCausalLM(Qwen2Config.tiny())
    raise SystemExit(f"unknown --model {name!r}")


def parse_range(s: str):
    lo, _, hi = s.partition(",")
    lo = int(lo)
    return (lo, int(hi) if hi else lo)


def run_storm(args, model, params, max_len, reqs, arrivals, writer,
              spec):
    """--router fleet storm: N solo engines, or --disagg tiers with
    ring KV migration, behind the deterministic router."""
    import numpy as np

    from pytorch_distributed_tpu.serve import (
        EngineConfig, InProcPrefixStore, Router, ServeEngine, drive,
    )

    store = InProcPrefixStore() if args.store else None

    def mk(role, eid):
        return ServeEngine(
            model, params,
            EngineConfig(num_slots=args.slots, max_len=max_len,
                         prefill_chunk=args.prefill_chunk,
                         page_size=args.page_size,
                         num_pages=args.num_pages,
                         role=role, engine_id=eid),
            spec=spec if role == "solo" else None,
            prefix_store=store if role != "decode" else None,
            telemetry=None,
        )

    if args.disagg:
        n_pre = -(-args.engines // 2)
        prefill = [mk("prefill", f"p{i}") for i in range(n_pre)]
        decode = [
            mk("decode", f"d{i}") for i in range(args.engines - n_pre)
        ]
        for e in prefill + decode:
            e.telemetry.writer = writer
        router = Router(prefill=prefill, decode=decode, writer=writer,
                        store=store)
        shape = f"{n_pre} prefill + {args.engines - n_pre} decode"
    else:
        engines = [mk("solo", f"e{i}") for i in range(args.engines)]
        for e in engines:
            e.telemetry.writer = writer
        router = Router(engines=engines, writer=writer, store=store)
        shape = f"{args.engines} solo"
    router.warm_up(np.ones(1, np.int32))
    dt = drive(router, reqs, arrivals)
    if writer is not None:
        writer.close()
    s = router.summary()
    total_tokens = sum(
        e["completed_tokens"] for e in s["engines"].values()
    )
    print(f"model={args.model} fleet=[{shape}] max_len={max_len} "
          f"requests={args.requests} rate="
          f"{args.rate or 'closed-loop'} wall={dt:.2f}s")
    print(f"  tokens/s (fleet)   = {total_tokens / max(dt, 1e-9):.2f} "
          f"({total_tokens} completed tokens)")
    for q in (50, 95, 99):
        v = s.get(f"ttft_ms_p{q}")
        if v is not None:
            print(f"  ttft_ms_p{q:<8} = {v:.2f}")
    if args.disagg:
        print(f"  migration          = {s['migration_frames']} frames, "
              f"{s['migration_bytes']:,d} wire B "
              f"({s['migration_payload_bytes']:,d} KV payload B)")
    if s["replays"] or s["lost_engines"]:
        print(f"  replays            = {s['replays']} "
              f"(lost engines: {s['lost_engines']})")
    if store is not None:
        st = store.stats()
        print(f"  prefix store       = {st['puts']} puts "
              f"({st['hits']} hits, {st['dup_puts']} dup puts, "
              f"{st['entries']} resident pages)")
    for eid, es in s["engines"].items():
        done = es.get("completed", 0)
        print(f"  [{eid}] completed={done} "
              f"tokens={es['completed_tokens']} "
              + (f"p99={es['ttft_ms_p99']:.1f}ms"
                 if "ttft_ms_p99" in es else ""))
    if args.log:
        print(f"telemetry JSONL -> {args.log}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--model", default="gpt2-tiny",
                    help="gpt2-tiny | gpt2-small | llama-tiny | qwen2-tiny")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="offered requests/sec (0 = submit all up front)")
    ap.add_argument("--poisson", action="store_true",
                    help="Poisson arrivals instead of uniform spacing")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=0,
                    help="per-slot KV capacity (0 = fit the workload)")
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--prompt-len", type=parse_range, default=(4, 16),
                    metavar="LO[,HI]")
    ap.add_argument("--new-tokens", type=parse_range, default=(8, 32),
                    metavar="LO[,HI]")
    ap.add_argument("--prefix-share", type=float, default=0.0,
                    help="fraction of requests opening with one common "
                    "system prompt (exercises the paged pool's "
                    "copy-free prefix sharing)")
    ap.add_argument("--prefix-len", type=int, default=16,
                    help="length of the shared system prompt")
    ap.add_argument("--page-size", type=int, default=None,
                    help="KV page size (default: auto divisor of max_len)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="KV pool pages (default: parity with the old "
                    "fixed [slots, max_len] pool)")
    ap.add_argument("--long-context", action="store_true",
                    help="preset: size max_len WELL past the live "
                    "lengths (4x the workload fit, >= 256, capped at "
                    "the model's position table) — the regime paged "
                    "attention exists for; the summary's decode "
                    "buckets show the tick running at the live lengths' "
                    "page width, not max_len's")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="enable speculative decoding with k draft "
                    "tokens per tick (draft = a randomly initialized "
                    "1-layer sibling — measures ENGINE mechanics, the "
                    "acceptance rate of a real trained draft will "
                    "differ)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--top-p", type=float, default=None)
    ap.add_argument("--deadline-s", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log", default=None,
                    help="telemetry JSONL path (MetricsWriter stream)")
    ap.add_argument("--engines", type=int, default=1,
                    help="fleet size for --router storm mode")
    ap.add_argument("--router", action="store_true",
                    help="drive --engines N engines behind the "
                    "deterministic telemetry-driven router")
    ap.add_argument("--disagg", action="store_true",
                    help="split the fleet into prefill/decode tiers "
                    "(half each, prefill rounded up) with ring KV "
                    "migration between them; implies --router")
    ap.add_argument("--store", action="store_true",
                    help="share one cross-engine prefix store across "
                    "the fleet (hot prompts prefilled once per fleet)")
    args = ap.parse_args()
    if args.disagg:
        args.router = True
    if args.router and args.engines < 2:
        ap.error("--router needs --engines >= 2 (a 1-engine fleet is "
                 "just the solo path — drop --router)")
    if args.disagg and args.spec_k:
        ap.error("--disagg refuses --spec-k: tiered speculation is not "
                 "supported (the draft cache does not ride the "
                 "migration frame)")
    if args.store and not args.router:
        ap.error("--store is a FLEET feature (cross-engine registry) — "
                 "a single engine already has its local page registry; "
                 "add --router --engines N")
    if args.long_context and args.max_len:
        # the preset's whole job is sizing max_len; honoring both would
        # either silently drop the preset or silently rewrite an
        # explicit --max-len — refused, like every contradictory-flag
        # combination in this repo
        ap.error("--long-context sizes max_len itself — pass one of "
                 "--long-context / --max-len, not both")

    import jax
    import numpy as np

    from pytorch_distributed_tpu.runtime.device import (
        enable_compilation_cache,
    )
    from pytorch_distributed_tpu.serve import (
        EngineConfig, ServeEngine, ServeTelemetry, SpecConfig, drive,
        prefix_shared_requests, uniform_arrivals, warm_up,
    )

    enable_compilation_cache()
    model = build_model(args.model)
    vocab = model.config.vocab_size
    rng = np.random.default_rng(args.seed)
    reqs = prefix_shared_requests(
        rng, args.requests, vocab,
        prompt_len=args.prompt_len, new_tokens=args.new_tokens,
        prefix_share=args.prefix_share,
        shared_prefix_len=args.prefix_len if args.prefix_share else 0,
        temperature=args.temperature, top_k=args.top_k,
        top_p=args.top_p, deadline_s=args.deadline_s,
    )
    if args.rate > 0 and args.poisson:
        gaps = rng.exponential(1.0 / args.rate, size=args.requests)
        arrivals = list(np.cumsum(gaps) - gaps[0])
    else:
        arrivals = uniform_arrivals(args.requests, args.rate)

    # auto max_len fits the workload AND the shared warm-up (a 1-token
    # prompt rounds to one chunk + the 2 tokens that force the decode
    # compile); an EXPLICIT --max-len is never silently rewritten — if
    # it can't hold the warm-up, warm_up's submit fails loudly
    max_len = args.max_len or max(
        [
            -(-r.prompt_len // args.prefill_chunk) * args.prefill_chunk
            + r.max_new_tokens + args.spec_k
            for r in reqs
        ] + [args.prefill_chunk + 2 + args.spec_k]
    )
    if args.long_context and not args.max_len:
        # the long-context mix: a pool sized far past the live lengths
        # (capped at the model's position table) so the decode tick's
        # bucket, not max_len, sets what a tick reads
        from pytorch_distributed_tpu.generation import model_max_len

        limit = model_max_len(model) or 1 << 30
        max_len = min(max(4 * max_len, 256), limit)
        if args.page_size:
            # align DOWN while still at the cap — the generic round-UP
            # below must never push a limit-capped max_len past the
            # model's position table (engine construction would refuse)
            max_len = max(
                max_len - max_len % args.page_size, args.page_size
            )
    if not args.max_len and args.page_size:
        # only the AUTO-computed fit is rounded up to a page multiple;
        # an explicit --max-len is never silently rewritten — if it
        # doesn't divide by --page-size, EngineConfig refuses loudly
        max_len = -(-max_len // args.page_size) * args.page_size
    writer = None
    if args.log:
        from pytorch_distributed_tpu.train.metrics import MetricsWriter
        writer = MetricsWriter(args.log)

    import jax.numpy as jnp  # noqa: F401 — backend init before timing

    params = model.init(
        jax.random.key(0),
        np.zeros((1, min(8, max_len - 1)), np.int32),
    )["params"]
    spec = None
    if args.spec_k:
        import dataclasses as _dc

        dcfg = _dc.replace(
            model.config, num_layers=1,
            hidden_size=max(model.config.hidden_size // 2, 16),
        )
        draft = type(model)(dcfg)
        dparams = draft.init(
            jax.random.key(1),
            np.zeros((1, min(8, max_len - 1)), np.int32),
        )["params"]
        spec = SpecConfig(draft, dparams,
                          num_draft_tokens=args.spec_k)
    if args.router:
        run_storm(args, model, params, max_len, reqs, arrivals,
                  writer, spec)
        return
    engine = ServeEngine(
        model, params,
        EngineConfig(num_slots=args.slots, max_len=max_len,
                     prefill_chunk=args.prefill_chunk,
                     page_size=args.page_size,
                     num_pages=args.num_pages),
        spec=spec,
    )
    # serve.loadgen's shared warm-up/pacing: both programs compile
    # outside the measured window, the JSONL stream starts clean, and
    # the pacing matches bench.py's serving phase exactly
    warm_up(engine, np.ones(1, np.int32),
            telemetry=ServeTelemetry(writer=writer))
    dt = drive(engine, reqs, arrivals)

    if writer is not None:
        writer.close()
    s = engine.telemetry.summary()
    print(f"model={args.model} slots={args.slots} max_len={max_len} "
          f"requests={args.requests} rate="
          f"{args.rate or 'closed-loop'} wall={dt:.2f}s")
    for k in sorted(s):
        v = s[k]
        print(f"  {k:>18} = {v:.2f}" if isinstance(v, float)
              else f"  {k:>18} = {v}")
    pool = engine.pool
    print(f"  decode compiles    = {engine.decode_compiles} "
          f"(bounded-compile invariant: one per occupied length "
          f"bucket, buckets={sorted(engine.decode_buckets)} pages)")
    print(f"  kv pages           = {pool.peak_pages} peak / "
          f"{pool.num_pages} total (page_size={pool.page_size})")
    print(f"  prefix hit rate    = {pool.prefix_hit_rate:.3f} "
          f"({pool.prefix_hits}/{pool.prefix_lookups} admissions, "
          f"{pool.shared_tokens} prompt tokens served copy-free)")
    if engine.spec is not None and engine.spec_verifies:
        print(f"  spec accept/verify = "
              f"{engine.spec_accepted / engine.spec_verifies:.2f} "
              f"(k={engine.spec.num_draft_tokens}, "
              f"{engine.spec_verifies} verifies, "
              f"{engine.spec_accepted}/{engine.spec_drafted} drafts "
              f"accepted)")
    if args.log:
        print(f"telemetry JSONL -> {args.log}")


if __name__ == "__main__":
    main()
