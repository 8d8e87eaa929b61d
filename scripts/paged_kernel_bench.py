#!/usr/bin/env python3
"""The paged-attention kernel alone on the chip, at the four serving
cells' shapes and realistic lengths, over the candidate block sizes.

    python scripts/paged_kernel_bench.py                 # on the chip
    python scripts/paged_kernel_bench.py --repo _export/parent

``--repo`` times the kernel of another checkout (the parent commit's
single-page body has no block size: it is timed as it is). One JSON line
a shape and block size: microseconds a call (one layer's call; the
median of 10 runs of a jitted loop of 64 calls over the pool's 4 planes,
each run ending in ``block_until_ready``: a run's dispatch and wait,
~0.6 ms here, is a hundredth of a millisecond a call; the ``idle`` case
bounds it and the call's own XLA operations from above), the share of the call's roofline —
bytes over 819 GB/s, or for latent pages the larger of that and the
operations over 197 TFLOP/s, both counted from the rows' lengths as
``perfbench/roofline`` counts them — and, at the derived block size, how
far the kernel is from the exact ``gather`` impl. PERF.md §6 (PR 28)
records what a v5e read and why ``block_pages`` derives what it does."""

import argparse
import inspect
import json
import math
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9
LAYERS, CALLS, PAGE = 4, 64, 16
BLOCK_TOKENS = (64, 128, 256, 512, 1024)


def _log_uniform(rng, lo, hi, size):
    return [int(math.exp(x)) for x in rng.uniform(
        math.log(lo), math.log(hi), size)]


def cases(rng, toy=False):
    """name -> rows, heads, frame, bucket and the tick's lengths (None:
    the slot is not decoding), as PERF.md §4-§5 describe the cells;
    ``toy``: an eighth of the rows and a sixteenth of the lengths."""
    sat = _log_uniform(rng, 300, 3500, 32)           # ~31% of 4096 live
    docs = [int(x) for x in rng.integers(2112, 2433, 32)]
    giga = _log_uniform(rng, 250, 6000, 109) + [None] * 19
    chat = [None] * 48
    for slot, n in zip((5, 17, 40), (150, 260, 400)):
        chat[slot] = n
    mistral = dict(Hq=32, Hkv=8, D=128, Dv=128, latent=False)
    out = {
        "sat": dict(mistral, lengths=sat, n_pages=256),
        "docs": dict(mistral, lengths=docs, n_pages=256),
        "giga": dict(Hq=64, Hkv=1, D=640, Dv=512, latent=True,
                     lengths=giga, n_pages=512),
        "chat": dict(Hq=16, Hkv=16, D=64, Dv=64, latent=False,
                     lengths=chat, n_pages=32),
        # no slot decoding: what the walk costs before any page
        "idle": dict(Hq=16, Hkv=16, D=64, Dv=64, latent=False,
                     lengths=[None] * 48, n_pages=32),
    }
    if toy:
        for case in out.values():
            case["lengths"] = [
                None if n is None else n // 16 for n in case["lengths"][::8]
            ]
            case["n_pages"] //= 16
    return out


def call_floor_s(case):
    """The least time one call may take, from the rows' lengths."""
    keys = sum(n + 1 for n in case["lengths"] if n is not None)
    rows = sum(n is not None for n in case["lengths"])
    H, D = case["Hq"], case["D"]
    if case["latent"]:  # roofline/latent_moe.latent_attention_call
        r, dr = 512, 64
        flops = 2 * H * (2 * r + dr) * keys
        nbytes = 2 * ((r + dr) * keys + rows * H * (2 * r + dr))
        return max(flops / PEAK_FLOPS, nbytes / PEAK_BYTES)
    # roofline/flops.paged_attention_call: bandwidth-bound
    nbytes = 2 * case["Hkv"] * D * 2 * keys + 2 * rows * H * D * 2
    return nbytes / PEAK_BYTES


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=ROOT)
    ap.add_argument("--label", default=None)
    ap.add_argument("--cases", default="sat,docs,giga,chat,idle")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy sizes, kernel interpreted: the code's "
                         "rehearsal, no line of it is a measurement")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.repo))

    import jax
    import jax.numpy as jnp
    import numpy as np

    import pytorch_distributed_tpu.ops  # noqa: F401
    paged = sys.modules["pytorch_distributed_tpu.ops.paged_attention"]

    toy = args.rehearse_cpu
    if not toy and jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU here: a CPU run measures nothing")
    blocked = hasattr(paged, "block_pages")
    if blocked:  # what the module ships: the sweep's last entry
        shipped = paged._BLOCK_VMEM_BYTES, paged._BLOCK_MAX_TOKENS
    takes_keep = "keep" in inspect.signature(paged.paged_attention).parameters
    label = args.label or ("blocks" if blocked else "single-page")
    if toy:
        label += "-rehearsal"
    out_dir = os.path.join(ROOT, "chiprun_out", "paged_kernel_bench")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(out_dir, f"{label}.jsonl"), "w")
    rng = np.random.default_rng(28)
    for name, case in cases(rng, toy).items():
        if name not in args.cases.split(","):
            continue
        lengths = case["lengths"]
        B, n = len(lengths), case["n_pages"]
        F = case["Hkv"] * case["D"]
        keep = jnp.asarray([x is not None for x in lengths])
        lens = jnp.asarray([x or 0 for x in lengths], jnp.int32)
        # every row owns the pages it reaches, scattered over the pool
        need = [-(-(x + 1) // PAGE) if x is not None else 0 for x in lengths]
        frames = rng.permutation(sum(need)) + 1
        tables = np.zeros((B, n), np.int32)
        at = 0
        for b, m in enumerate(need):
            tables[b, :m] = frames[at:at + m]
            at += m
        tables = jnp.asarray(tables)
        P1 = sum(need) + 1
        kq, kk, kv = jax.random.split(jax.random.key(B), 3)
        q = jax.random.normal(kq, (B, 1, case["Hq"], case["D"]), jnp.bfloat16)
        k_pool = jax.random.normal(kk, (LAYERS, P1, PAGE, F), jnp.bfloat16)
        v_pool = None if case["latent"] else jax.random.normal(
            kv, (LAYERS, P1, PAGE, F), jnp.bfloat16)

        def one(layer, q, k_pool, v_pool, impl):
            v = (paged.PagedPrefix(k_pool, case["Dv"]) if v_pool is None
                 else v_pool)
            kw = {"keep": keep} if takes_keep else {}
            return paged.paged_attention(
                q, k_pool, v, page_tables=tables, lengths=lens, layer=layer,
                scale=case["D"] ** -0.5, impl=impl, **kw)

        def stack(impl):
            def run(q, k_pool, v_pool):
                def body(i, acc):
                    return acc + one(
                        i % LAYERS, q, k_pool, v_pool, impl
                    ).astype(jnp.float32)
                return jax.lax.fori_loop(
                    0, CALLS, body,
                    jnp.zeros((B, 1, case["Hq"], case["Dv"]), jnp.float32))
            return jax.jit(run)

        floor = call_floor_s(case)
        exact = np.asarray(jax.jit(
            lambda *a: one(jnp.int32(1), *a, "gather")
        )(q, k_pool, v_pool), np.float32)
        sweep = (BLOCK_TOKENS + (None,)) if blocked else (None,)
        for tokens in sweep[-2:] if toy else sweep:
            if blocked:
                # block_pages derives from two constants: lift the byte
                # budget and cap the tokens, or put both back
                paged._BLOCK_VMEM_BYTES, paged._BLOCK_MAX_TOKENS = (
                    shipped if tokens is None else (1 << 30, tokens)
                )
                k = paged.block_pages(PAGE, F * 2, n)
            else:
                k = 1
            fn = stack("kernel")
            try:
                jax.block_until_ready(fn(q, k_pool, v_pool))
            except Exception as e:  # a block the compiler refuses
                line = {"label": label, "case": name, "block": tokens,
                        "block_pages": k, "error": str(e)[:400]}
                print(json.dumps(line), flush=True)
                sink.write(json.dumps(line) + "\n")
                continue
            times = []
            for _ in range(2 if toy else 10):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(q, k_pool, v_pool))
                times.append(time.perf_counter() - t0)
            per_call = statistics.median(times) / CALLS
            line = {
                "label": label, "case": name,
                "block": "derived" if tokens is None and blocked else tokens,
                "block_pages": k, "us_per_call": per_call * 1e6,
                "roofline_share_pct": 100 * floor / per_call,
                "floor_us": floor * 1e6,
                "rows": int(keep.sum()), "slots": B, "bucket_pages": n,
                "live_pages": sum(need),
            }
            if tokens is None:
                got = np.asarray(jax.jit(
                    lambda *a: one(jnp.int32(1), *a, "kernel")
                )(q, k_pool, v_pool), np.float32)
                rows = np.asarray(keep)
                if rows.any():
                    line["max_abs_difference"] = float(
                        np.max(np.abs(got[rows] - exact[rows])))
                    line["output_scale"] = float(
                        np.max(np.abs(exact[rows])))
            print(json.dumps(line), flush=True)
            sink.write(json.dumps(line) + "\n")
        del k_pool, v_pool
    sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
