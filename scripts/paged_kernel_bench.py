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
records what a v5e read and why ``block_pages`` derives what it does.

``--chunks`` times a prompt CHUNK's attention instead (PR 30): one row's
``C`` queries at ``start`` over its pages, at the four cells' shapes
(and GPT-2's chunk of 64 in ``chip_smoke.py``: ``smoke``), the ways the
checkout has them — ``tiled``, the kernel's query-tiled body over the
row's live pages; ``einsum``, the ``gather`` impl: the bucket-wide slab
and XLA's dense math, the chunk's attention before PR 30 (so ``--repo
<parent>`` times the parent's); and for latent frames ``decoded``
alone, the bucket's latents gathered, decoded to keys and values, and
the dense math: what ``MLAttention`` runs for a chunk. (PERF.md §6,
PR 30, has the reading of the ABSORBED form through a tiled body for
the one latent head that decided against it; that body was not kept.)
The floor counts the causal half of
the chunk's own block: ``start * C + C (C + 1) / 2`` query-key pairs a
head, the row's ``start + C`` frames read once."""

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
TILE_ROWS = (1024, 2048)


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


def median_run_s(fn, args, runs):
    """Median wall time of ``runs`` calls of a compiled ``fn``, each
    ending in ``block_until_ready``."""
    import jax

    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def chunk_cases(toy=False):
    """name -> heads, frame, chunk, the bucket's pages and the chunk's
    ``start``, as the engine would dispatch it (the bucket is the power
    of two of pages that covers ``start + C``)."""
    mistral = dict(Hq=32, Hkv=8, D=128, Dv=128, latent=False, C=512,
                   window=4096, max_pages=256)
    giga = dict(Hq=64, Hkv=1, D=640, Dv=512, latent=True, C=512,
                window=None, max_pages=512)
    out = {}
    for start in (0, 512, 1536, 2560):
        out[f"sat@{start}"] = dict(mistral, start=start)
    out["docs@2048"] = dict(mistral, start=2048)
    for start in (0, 1024, 4096):
        out[f"giga@{start}"] = dict(giga, start=start)
    out["chat@0"] = dict(Hq=16, Hkv=16, D=64, Dv=64, latent=False, C=128,
                         window=None, max_pages=64, start=0)
    out["chat@384"] = dict(out["chat@0"], start=384)
    out["smoke@0"] = dict(out["chat@0"], C=64)
    out["smoke@448"] = dict(out["chat@0"], C=64, start=448)
    if toy:
        for case in out.values():
            hkv = max(case["Hkv"] // 8, 1)   # an eighth of the kv heads,
            case.update(C=case["C"] // 16, start=case["start"] // 16,
                        max_pages=case["max_pages"] // 16, Hkv=hkv,
                        Hq=hkv * (case["Hq"] // case["Hkv"]))  # G kept
    for case in out.values():
        pages = -(-(case["start"] + case["C"]) // PAGE)
        case["n_pages"] = min(1 << (pages - 1).bit_length(),
                              case["max_pages"])
    return out


def chunk_floor_s(case):
    """The least time one layer's chunk attention may take."""
    C, start, H = case["C"], case["start"], case["Hq"]
    pairs = start * C + C * (C + 1) // 2
    flops = 2 * H * (case["D"] + case["Dv"]) * pairs
    pools = 1 if case["latent"] else 2
    nbytes = 2 * (
        pools * (start + C) * case["Hkv"] * case["D"]
        + C * H * (case["D"] + case["Dv"])
    )
    return max(flops / PEAK_FLOPS, nbytes / PEAK_BYTES)


def run_chunks(args, paged, label, sink, jax, jnp, np):
    from pytorch_distributed_tpu.ops.attention import dot_product_attention

    toy = args.rehearse_cpu
    tiled = hasattr(paged, "_paged_tiled_call")
    shipped_rows = getattr(paged, "_TILE_ROWS", None)
    rng = np.random.default_rng(30)
    for name, case in chunk_cases(toy).items():
        if name.split("@")[0] not in args.cases.split(","):
            continue
        C, start, n = case["C"], case["start"], case["n_pages"]
        # a run's dispatch and wait (~0.6 ms) over its calls: under a
        # hundredth of a millisecond a call, under 1% of a latent one
        calls = 2 if toy else 16 if case["latent"] else CALLS
        H, D, Dv, F = case["Hq"], case["D"], case["Dv"], (
            case["Hkv"] * case["D"])
        # the row owns the pages it reaches, scattered over a pool that
        # holds as many again
        own = -(-(start + C) // PAGE)
        P1 = 2 * own + 1
        table = np.zeros((1, n), np.int32)
        table[0, :own] = rng.permutation(2 * own)[:own] + 1
        table = jnp.asarray(table)
        lens = jnp.asarray([start], jnp.int32)
        kq, kk, kv, kw = jax.random.split(jax.random.key(start + C), 4)
        q = jax.random.normal(kq, (1, C, H, D), jnp.bfloat16)
        k_pool = jax.random.normal(kk, (LAYERS, P1, PAGE, F), jnp.bfloat16)
        v_pool = None if case["latent"] else jax.random.normal(
            kv, (LAYERS, P1, PAGE, F), jnp.bfloat16)
        scale = (192 if case["latent"] else D) ** -0.5

        def attend(layer, q, k_pool, v_pool, impl):
            v = (paged.PagedPrefix(k_pool, Dv) if v_pool is None
                 else v_pool)
            return paged.paged_attention(
                q, k_pool, v, page_tables=table, lengths=lens, layer=layer,
                scale=scale, window=case["window"], impl=impl)

        forms = {"einsum": lambda *a: attend(*a, "gather")}
        if tiled:
            forms["tiled"] = lambda *a: attend(*a, "kernel")
        if case["latent"]:
            # MLAttention's form of a chunk (models/deepseek_v3.py) over
            # the cached frames: r latent + dr rotary lanes
            r, dr, dn, dv = 512, 64, 128, 192
            if toy:
                dn, dv = 16, 24
            w_ukv = 0.02 * jax.random.normal(
                kw, (r, H, dn + dv), jnp.bfloat16)
            w_uk, w_uv = w_ukv[..., :dn], w_ukv[..., dn:]

            def decoded(layer, q, k_pool, v_pool):
                q_n, q_r = q[..., :dn], q[..., dn:dn + dr]
                rows = k_pool[layer, table[0]].reshape(1, n * PAGE, D)
                c_all, kr_all = rows[..., :r], rows[..., None, r:r + dr]
                k_n = jnp.einsum("btr,rhd->bthd", c_all, w_uk)
                v = jnp.einsum("btr,rhd->bthd", c_all, w_uv)
                k = jnp.concatenate([
                    k_n, jnp.broadcast_to(kr_all, k_n.shape[:-1] + (dr,)),
                ], axis=-1)
                return dot_product_attention(
                    jnp.concatenate([q_n, q_r], axis=-1), k, v,
                    causal=True, scale=scale, q_offset=lens)

            forms = {"decoded": decoded}

        floor = chunk_floor_s(case)
        outs = {}
        # the tiled forms over the candidate tiles, the shipped one last
        sweep = [
            (form, fn, rows) for form, fn in forms.items()
            for rows in ((None,) if form in ("einsum", "decoded") or toy
                         else TILE_ROWS + (None,))
        ]
        for form, fn, tile_rows in sweep:
            if tiled:
                paged._TILE_ROWS = tile_rows or shipped_rows

            def run(q, k_pool, v_pool, fn=fn):
                def body(i, acc):
                    # the queries change a call, so no call is hoisted
                    out = fn(i % LAYERS, q + acc[..., :1].astype(q.dtype),
                             k_pool, v_pool)
                    return 1e-3 * out[..., :8].astype(jnp.float32)
                return jax.lax.fori_loop(
                    0, calls, body, jnp.zeros((1, C, H, 8), jnp.float32))
            run = jax.jit(run)
            line = {"label": label, "case": name, "form": form,
                    "tile_rows": tile_rows or "shipped",
                    "bucket_pages": n, "live_pages": own,
                    "floor_us": floor * 1e6}
            try:
                jax.block_until_ready(run(q, k_pool, v_pool))
                per_call = median_run_s(
                    run, (q, k_pool, v_pool), 2 if toy else 10) / calls
                line.update(us_per_call=per_call * 1e6,
                            roofline_share_pct=100 * floor / per_call)
                outs[form] = np.asarray(jax.jit(
                    lambda *a, fn=fn: fn(jnp.int32(1), *a)
                )(q, k_pool, v_pool), np.float32)
            except Exception as e:  # a shape the compiler refuses
                line["error"] = str(e)[:400]
            if form in outs and len(outs) > 1:
                first = next(iter(outs.values()))
                line["max_abs_difference"] = float(
                    np.max(np.abs(outs[form] - first)))
                line["output_scale"] = float(np.max(np.abs(first)))
            print(json.dumps(line), flush=True)
            sink.write(json.dumps(line) + "\n")
        del k_pool, v_pool


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=ROOT)
    ap.add_argument("--label", default=None)
    ap.add_argument("--cases", default="sat,docs,giga,chat,smoke,idle")
    ap.add_argument("--chunks", action="store_true",
                    help="a prompt chunk's attention, not the tick's")
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
    if args.chunks:
        label += "-chunks"
    sink = open(os.path.join(out_dir, f"{label}.jsonl"), "w")
    if args.chunks:
        run_chunks(args, paged, label, sink, jax, jnp, np)
        sink.close()
        return 0
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
            per_call = median_run_s(
                fn, (q, k_pool, v_pool), 2 if toy else 10) / CALLS
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
