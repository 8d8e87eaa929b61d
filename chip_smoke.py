#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

One process drives the two main paths once, through the entry points a
user calls, on GPT-2-medium at its full published size (24 layers,
hidden 1024, 16 heads of 64, vocab 50257, 1024 positions; weights random
from a seed):

1. kernels  — the Pallas paged-attention and flash-attention kernels,
   compiled by Mosaic (never interpreted), against their in-repo
   references at the smoke model's geometry and at the 32/8 x 128 GQA
   geometry the first benchmark cells bring; and a bf16 head at
   Mistral's shape, fused with its cast to f32, still handing on bf16's
   values (``Policy.to_output``: what a tick's greedy token rests on);
2. train    — ``recipes/gpt2_zero1.main`` itself: ZeRO-1 over every
   local chip, sequence 1024, remat, global batch 8 per chip;
3. serve    — a ``ServeEngine`` driven by the calls
   ``scripts/serve_loadgen.py`` makes (``warm_up``, ``drive``,
   ``prefix_shared_requests``): 8 slots, max_len 1024, 16-token pages.

Every check is an assertion and nothing is caught: a failed phase is a
traceback and a non-zero exit. The last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every
phase passed. Wall and compile seconds are printed as set-up facts, not
as metrics — this script measures nothing.

    python chip_smoke.py                 # the chip run; needs a TPU
    python chip_smoke.py --rehearse-cpu  # the same code at toy size on
                                         # the CPU, kernels interpreted:
                                         # proves nothing about the chip
                                         # and prints no result line

With no argument a platform other than ``tpu`` is an error before any
model is built. The chip belongs to one process: this one starts no
child that needs it.
"""

import argparse
import dataclasses
import importlib.metadata
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that differs between the chip run and the rehearsal."""

    gpt2: str            # recipes/gpt2_zero1.py --size
    seq_len: int
    batch_per_chip: int
    train_steps: int
    slots: int
    max_len: int
    page_size: int
    prefill_chunk: int
    requests: int
    prompt_len: tuple    # tail length range; shared requests add the prefix
    new_tokens: tuple
    prefix_len: int
    # kernel section: (batch, context) and the two head geometries
    kernel_batch: int
    kernel_ctx: int
    geometries: tuple    # (Hq, Hkv, D, dtype name, window)
    head: tuple          # (rows, hidden, vocab) of a bf16 head's product


FULL = Sizes(
    gpt2="medium", seq_len=1024, batch_per_chip=8, train_steps=5,
    slots=8, max_len=1024, page_size=16, prefill_chunk=64, requests=16,
    prompt_len=(64, 512), new_tokens=(32, 128), prefix_len=128,
    kernel_batch=8, kernel_ctx=1024,
    geometries=(
        (16, 16, 64, "float32", None),     # the smoke model's
        (32, 8, 128, "bfloat16", 256),     # GQA + window (Mistral widths)
    ),
    head=(32, 4096, 32000),                # mistral-serve-sat's tick
)
REHEARSAL = Sizes(
    gpt2="tiny", seq_len=32, batch_per_chip=2, train_steps=3,
    slots=4, max_len=64, page_size=16, prefill_chunk=8, requests=6,
    prompt_len=(4, 12), new_tokens=(4, 8), prefix_len=16,
    kernel_batch=2, kernel_ctx=64,
    geometries=(
        (4, 4, 16, "float32", None),
        (8, 2, 32, "bfloat16", 24),
    ),
    head=(4, 64, 512),
)

# Kernel-vs-reference tolerances, as max |kernel - ref| / max |ref|, the
# reference traced under jax.default_matmul_precision("highest") (so are
# the kernels' f32 dots; their bf16 dots stay single-pass, ops/
# flash_attention._mxu_dot).
# float32: forward, the two differ only in the ORDER of an online-softmax
#   reduction over <= 1024 keys and in exp's last bits — measured <= 2.3e-6
#   on a v5e. The backward recomputes p = exp(s - lse) from the stored
#   f32 lse, which amplifies those last bits by the logit scale — measured
#   <= 6.1e-5. 3e-4 is five times the worst measured.
# bfloat16: inputs are identical bf16 values on both sides, but the
#   kernel rounds UNNORMALISED probabilities to bf16 before the p @ v
#   matmul and the reference rounds NORMALISED ones, so each differs from
#   exact by up to 2^-8 relative per term — measured <= 7.7e-3 on a v5e;
#   2e-2 is the bound the CPU parity tests pin for the same reason
#   (tests/test_paged_attention.py).
TOL = {"float32": 3e-4, "bfloat16": 2e-2}


class CompileClock:
    """Seconds JAX spent in backend compilation, from its own monitoring
    events (a persistent-cache hit is inside the same event and costs
    milliseconds, which is how a warm second run shows)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def lap(self):
        out = (self.seconds, self.count)
        self.seconds, self.count = 0.0, 0
        return out


def _rel_err(got, ref):
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    assert np.isfinite(got).all(), "kernel produced non-finite values"
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def _is_compiled_kernel(jitted, *args) -> bool:
    return "tpu_custom_call" in jitted.lower(*args).as_text()


def check_kernels(sizes: Sizes, on_chip: bool) -> None:
    """Paged and flash kernels against ``_paged_gather`` /
    ``dot_product_attention`` at every geometry in ``sizes``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_tpu.ops.attention import dot_product_attention
    from pytorch_distributed_tpu.ops.flash_attention import flash_attention
    from pytorch_distributed_tpu.ops.paged_attention import paged_attention

    B, ctx, ps = sizes.kernel_batch, sizes.kernel_ctx, sizes.page_size
    n = ctx // ps
    rng = np.random.default_rng(0)
    with jax.default_matmul_precision("highest"):
        for Hq, Hkv, D, dtype_name, window in sizes.geometries:
            dtype = jnp.dtype(dtype_name)
            tol = TOL[dtype_name]

            def rand(*shape):
                return jnp.asarray(rng.standard_normal(shape), dtype)

            # -- paged decode (W=1), speculative verify (W=5) and a prompt
            # chunk (the kernel's query-tiled body, PR 30: W=128, and 96,
            # no whole tile of two-heads-a-group positions) ---------------
            # the pool as it is stored: frames lane-dense, [ps, Hkv * D]
            k_pages = rand(B * n + 1, ps, Hkv * D).at[0].set(0)
            v_pages = rand(B * n + 1, ps, Hkv * D).at[0].set(0)
            tables = jnp.asarray(
                rng.permutation(np.arange(1, B * n + 1)).reshape(B, n),
                jnp.int32,
            )
            for W in (1, 5, min(96, ctx // 4), min(128, ctx // 2)):
                q = rand(B, W, Hq, D)
                lengths = jnp.asarray(
                    rng.integers(0, ctx - W + 1, size=B), jnp.int32
                )

                kernel, gather = (
                    jax.jit(lambda q, k, v, t, l, impl=impl: paged_attention(
                        q, k, v, page_tables=t, lengths=l, window=window,
                        impl=impl,
                    )) for impl in ("kernel", "gather")
                )
                args = (q, k_pages, v_pages, tables, lengths)
                if on_chip:
                    assert _is_compiled_kernel(kernel, *args)
                err = _rel_err(kernel(*args), gather(*args))
                print(f"  paged  W={W} {Hq}/{Hkv}x{D} {dtype_name} "
                      f"window={window}: rel err {err:.2e} (tol {tol:.0e})",
                      flush=True)
                assert err <= tol

            # -- flash forward + backward: plain, packed, key-masked ------
            S = ctx
            q, k, v = rand(B, S, Hq, D), rand(B, S, Hkv, D), rand(B, S, Hkv, D)
            cot = rand(B, S, Hq, D)
            # 3 documents per row, boundaries at random multiples of 8
            cuts = np.sort(rng.integers(1, S // 8, size=(B, 2)) * 8, axis=1)
            seg = jnp.asarray(
                (np.arange(S)[None] >= cuts[:, :1]).astype(np.int32)
                + (np.arange(S)[None] >= cuts[:, 1:]), jnp.int32,
            )
            # right-padded rows: the first len_b keys are real (len_b >= 1
            # keeps key 0, so no causal row is ever fully masked)
            lens = rng.integers(S // 2, S + 1, size=B)
            kv_mask = jnp.asarray(np.arange(S)[None] < lens[:, None])
            for name, fkw, rkw in (
                ("plain", {}, {}),
                ("segment_ids", {"segment_ids": seg}, {"segment_ids": seg}),
                ("kv_mask", {"kv_mask": kv_mask}, {"mask": kv_mask}),
            ):
                def both(fn, kw):
                    def loss(q, k, v):
                        out = fn(q, k, v, causal=True, **kw)
                        return jnp.sum(
                            out.astype(jnp.float32)
                            * cot.astype(jnp.float32)
                        ), out
                    return jax.jit(jax.value_and_grad(
                        loss, argnums=(0, 1, 2), has_aux=True
                    ))

                flash, ref = both(flash_attention, fkw), both(
                    dot_product_attention, rkw
                )
                if on_chip:
                    assert _is_compiled_kernel(flash, q, k, v)
                (_, out), grads = flash(q, k, v)
                (_, out_r), grads_r = ref(q, k, v)
                errs = [_rel_err(out, out_r)] + [
                    _rel_err(g, gr) for g, gr in zip(grads, grads_r)
                ]
                print(f"  flash  {name:<11} B={B} S={S} {Hq}/{Hkv}x{D} "
                      f"{dtype_name}: rel err out/dq/dk/dv "
                      + "/".join(f"{e:.2e}" for e in errs)
                      + f" (tol {tol:.0e})", flush=True)
                assert max(errs) <= tol


def check_head_rounding(sizes: Sizes) -> None:
    """A head whose product is bf16 hands on bf16's values, fused or not.

    ``Policy.to_output`` (the cast to f32, then ``reduce_precision``) is
    what Llama's and DeepSeek-V3's heads return their logits through:
    compiled into ONE program with the product, every f32 logit has to
    be a bf16 number and their ``argmax`` the one a program reads that
    wrote the bf16 logits to memory first. With a bare ``astype`` the
    compiler hands on the product's f32 accumulator (0.005% of the
    logits were bf16 numbers at this shape on a v5e, PERF.md §6 PR 33)
    and a tick's greedy token depends on how its head was fused."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_tpu.runtime.precision import Policy

    rows, hidden, vocab = sizes.head
    kx, kw = jax.random.split(jax.random.key(0))
    x = jax.random.normal(kx, (rows, hidden), jnp.bfloat16)
    w = (jax.random.normal(kw, (hidden, vocab)) * 0.02).astype(jnp.bfloat16)

    @jax.jit
    def fused(x, w):
        logits = Policy().to_output(x @ w)
        return logits, jnp.argmax(logits, axis=-1)

    logits, token = fused(x, w)
    stored = jax.jit(lambda x, w: x @ w)(x, w)   # bf16, through memory
    share = float(np.mean(
        np.asarray(logits)
        == np.asarray(logits.astype(jnp.bfloat16).astype(jnp.float32))
    ))
    same = int(np.sum(
        np.asarray(token) == np.asarray(jnp.argmax(stored, axis=-1))
    ))
    print(f"  head   {rows}x{hidden}x{vocab} bfloat16: {share:.4%} of the "
          f"fused f32 logits are bf16 numbers; argmax equal to the stored "
          f"product's in {same}/{rows} rows", flush=True)
    assert share == 1.0 and same == rows
    assert np.array_equal(
        np.asarray(logits), np.asarray(stored.astype(jnp.float32))
    )


def train_leg(sizes: Sizes, extra_args=(), *, batch_size=None) -> dict:
    """``recipes/gpt2_zero1.main`` for a handful of steps; returns the
    facts it established (losses, compile counts, where the optimizer
    state sits, device memory)."""
    import jax

    import pytorch_distributed_tpu as ptd

    sys.path.insert(0, os.path.join(HERE, "recipes"))
    import gpt2_zero1

    from pytorch_distributed_tpu.models import GPT2Config
    from pytorch_distributed_tpu.train.metrics import read_metrics

    n_dev = jax.device_count()
    batch = batch_size or sizes.batch_per_chip * n_dev
    metrics_path = os.path.join(OUT_DIR, "train_metrics.jsonl")
    if os.path.exists(metrics_path):
        os.remove(metrics_path)
    argv = [
        "--size", sizes.gpt2, "--seq-len", str(sizes.seq_len), "--remat",
        "--batch-size", str(batch), "--accum-steps", "1",
        "--steps-per-epoch", str(sizes.train_steps), "--log-every", "1",
        "--metrics-path", metrics_path,
        "--trace-dir", os.path.join(OUT_DIR, "train_trace"),
        *extra_args,
    ]
    print(f"  recipes/gpt2_zero1.py {' '.join(argv)}", flush=True)
    state = gpt2_zero1.main(argv)
    records = read_metrics(metrics_path, strict=True)
    losses = [
        r["loss"] for r in records if r.get("split") == "train"
        and "loss" in r
    ]
    compiles = next(r for r in records if r.get("event") == "recompiles")
    vocab = getattr(GPT2Config, sizes.gpt2)().vocab_size
    print(f"  losses per step: {[round(x, 4) for x in losses]} "
          f"(ln vocab = {math.log(vocab):.3f})", flush=True)
    assert len(losses) == sizes.train_steps, records
    assert all(math.isfinite(x) for x in losses)
    # random tokens through a freshly initialised LM head: the first loss
    # is the entropy of a near-uniform distribution over the vocabulary,
    # plus about half the variance of the initial logits
    assert 0.0 <= losses[0] - math.log(vocab) < 1.0, losses[0]
    assert compiles["compiles.train.step"] == 1, compiles
    assert compiles["recompiles_total"] == 0, compiles

    # where the optimizer state really sits (ZeRO-1 shards it over dp)
    leaves = [
        x for x in jax.tree_util.tree_leaves(state.opt_state)
        if getattr(x, "ndim", 0) >= 1
    ]
    devices_per_leaf = {
        len({s.device for s in x.addressable_shards}) for x in leaves
    }
    total_bytes = sum(x.nbytes for x in leaves)
    sharded_bytes = sum(
        x.nbytes for x in leaves
        if x.addressable_shards[0].data.size < x.size
    )
    stats = ptd.memory_stats()
    del state
    for name, s in stats.items():
        if not s:  # XLA:CPU (the rehearsal) reports no allocator stats
            continue
        print(f"  {name}: bytes_in_use={s['bytes_in_use']} "
              f"peak_bytes_in_use={s['peak_bytes_in_use']} "
              f"bytes_limit={s['bytes_limit']}", flush=True)
        assert s["peak_bytes_in_use"] < s["bytes_limit"], (name, s)
    print(f"  optimizer state: {len(leaves)} array leaves, "
          f"{total_bytes} bytes, each leaf on {sorted(devices_per_leaf)} "
          f"device(s); {sharded_bytes} bytes "
          f"({sharded_bytes / total_bytes:.1%}) in leaves whose local "
          f"shard is smaller than the leaf", flush=True)
    assert devices_per_leaf == {n_dev}, devices_per_leaf
    # on several chips ZeRO-1 must really spread the state. Not all of
    # it: at dp=4 the moments of GPT-2's 50257-row embedding stay
    # replicated (14.5% of the bytes; no dp-divisible dim is found for
    # them — ROADMAP), so the bar is four fifths, not everything
    assert n_dev == 1 or sharded_bytes >= 0.8 * total_bytes
    ptd.destroy_process_group()
    return {
        "argv": argv, "devices": n_dev, "losses": losses,
        "compiles": compiles, "opt_state_bytes": total_bytes,
        "opt_state_sharded_bytes": sharded_bytes, "memory": stats,
    }


def serve_leg(sizes: Sizes, on_chip: bool) -> None:
    """A ``ServeEngine`` on the same model answering a seeded request
    mix the way ``scripts/serve_loadgen.py`` drives it."""
    import jax
    import numpy as np

    from pytorch_distributed_tpu.models import GPT2Config, GPT2LMHead
    from pytorch_distributed_tpu.ops.paged_attention import (
        resolve_paged_attention_impl,
        set_paged_attention_impl,
    )
    from pytorch_distributed_tpu.serve import (
        EngineConfig, ServeEngine, drive, prefix_shared_requests,
        uniform_arrivals, warm_up,
    )

    if not on_chip:
        # the rehearsal runs the kernel too, interpreted ("auto" would
        # pick the gather impl off-TPU and rehearse a different program)
        set_paged_attention_impl("kernel")
    assert resolve_paged_attention_impl() == "kernel"
    model = GPT2LMHead(getattr(GPT2Config, sizes.gpt2)())
    params = model.init(
        jax.random.key(0), np.zeros((1, 8), np.int32)
    )["params"]
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=sizes.slots, max_len=sizes.max_len,
        prefill_chunk=sizes.prefill_chunk, page_size=sizes.page_size,
    ))
    rng = np.random.default_rng(0)
    reqs = prefix_shared_requests(
        rng, sizes.requests, model.config.vocab_size,
        prompt_len=sizes.prompt_len, new_tokens=sizes.new_tokens,
        prefix_share=0.5, shared_prefix_len=sizes.prefix_len,
    )  # greedy (temperature 0), no eos: each emits exactly max_new_tokens
    asked = sum(r.max_new_tokens for r in reqs)
    print(f"  {len(reqs)} greedy requests, prompts "
          f"{min(r.prompt_len for r in reqs)}-"
          f"{max(r.prompt_len for r in reqs)} tokens, {asked} new tokens "
          f"asked in total", flush=True)

    t0 = time.perf_counter()
    warm_up(engine, np.ones(1, np.int32))
    warm_s = time.perf_counter() - t0
    buckets = sorted(engine.decode_buckets)
    warm_compiles = engine.decode_compiles
    assert warm_compiles == len(buckets) == len(engine._buckets), (
        warm_compiles, buckets,
    )
    wall = drive(engine, reqs, uniform_arrivals(len(reqs), 0.0))
    s = engine.telemetry.summary()
    pool = engine.pool
    print(f"  warm-up {warm_s:.1f}s (decode buckets {buckets} pages), "
          f"drive {wall:.1f}s; completed={s.get('completed')} "
          f"completed_tokens={s['completed_tokens']}", flush=True)
    print(f"  decode compiles = {engine.decode_compiles} (after warm-up "
          f"{warm_compiles}), prefill compiles = "
          f"{engine.prefill_compiles} over buckets "
          f"{sorted(engine.prefill_buckets)}", flush=True)
    print(f"  prefix hits = {pool.prefix_hits}/{pool.prefix_lookups} "
          f"admissions ({pool.shared_tokens} prompt tokens copy-free), "
          f"peak pages {pool.peak_pages}/{pool.num_pages}", flush=True)
    # every request completed, and together they emitted exactly what was
    # asked: none can emit more than its max_new_tokens, so each emitted
    # its own count
    assert s.get("completed") == len(reqs), s
    assert s["completed_tokens"] == asked, (s, asked)
    # one compile per length bucket, all of them in warm-up
    assert engine.decode_compiles == warm_compiles
    assert all(
        n == 1 for n in engine._decode_bucket_compiles.values()
    ), engine._decode_bucket_compiles
    assert pool.prefix_hits > 0
    pool.check_consistency()
    if on_chip:
        # a quiet route to the gather impl or to interpret mode would
        # leave no Mosaic call in the tick's program
        text = engine.trace_decode(buckets[-1]).lower().as_text()
        assert "tpu_custom_call" in text
        print("  decode program carries a Mosaic custom call "
              "(tpu_custom_call)", flush=True)
    stats = jax.local_devices()[0].memory_stats()
    if stats:
        print(f"  device 0: peak_bytes_in_use={stats['peak_bytes_in_use']} "
              f"bytes_limit={stats['bytes_limit']}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n")[0],
    )
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="run the same code at toy size on the CPU with the kernels "
        "interpreted; prints no result line",
    )
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    devices = jax.devices()
    dev = devices[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"platform={dev.platform} device_kind={dev.device_kind!r} "
          f"devices={len(devices)}", flush=True)
    print(f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu}", flush=True)
    want = "cpu" if args.rehearse_cpu else "tpu"
    if dev.platform != want:
        print(f"chip_smoke: the platform is {dev.platform!r}, not "
              f"{want!r}; nothing was run", file=sys.stderr)
        return 1
    on_chip = not args.rehearse_cpu
    sizes = FULL if on_chip else REHEARSAL

    import pytorch_distributed_tpu as ptd

    os.makedirs(OUT_DIR, exist_ok=True)
    print(f"compile cache: {ptd.enable_compilation_cache()}", flush=True)
    clock = CompileClock()
    t_start = time.perf_counter()
    for name, leg in (
        ("kernels", lambda: check_kernels(sizes, on_chip)),
        ("head", lambda: check_head_rounding(sizes)),
        ("train", lambda: train_leg(sizes)),
        ("serve", lambda: serve_leg(sizes, on_chip)),
    ):
        print(f"[{name}]", flush=True)
        t0 = time.perf_counter()
        leg()
        secs, count = clock.lap()
        print(f"[{name}] passed: wall {time.perf_counter() - t0:.1f}s, "
              f"of which backend compile {secs:.1f}s over {count} "
              f"programs", flush=True)
    print(f"all phases passed in {time.perf_counter() - t_start:.1f}s",
          flush=True)
    if on_chip:
        print(json.dumps({"ok": True, "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices),
        }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
