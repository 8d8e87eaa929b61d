"""First EXECUTED Llama-3-8B step: on-device int4 build + scan_dequant decode.

Recipe 5 (BASELINE.json:11, SURVEY.md §7 hard part c) is the one
blueprint row that has only ever been proven abstractly (AOT lowering,
v5p-64 fit, XLA-cost-analysis step projection — tests/test_llama8b.py).
This script turns it into an executed fact on the ONE
real chip: a full-architecture Llama-3-8B (128256 vocab, 32 scanned
layers, GQA 32/8, 14336 FFN) decoding real tokens through the
int4 + per-layer-scan-dequant serving path (ops/quant.py,
models/scan.py).

Why random weights are the honest play here: there is no egress to
fetch real checkpoints, and throughput/memory do not depend on weight
values. The weights are built DIRECTLY on device in the exact layout
``quantize_for_scan_dequant`` produces — never materializing a bf16/f32
8B tree anywhere (host RAM or HBM):

* scanned block kernels: per LAYER, generate one layer's f32 kernel on
  device, int4-quantize it there, free the float transient, stack the
  32 quantized slices. Groupwise int4 math is slice-invariant (scales
  reduce axis -2 per layer), so per-layer-quantize+stack is bitwise
  the layout the whole-tree quantizer emits on a stacked kernel — the
  tiny preset asserts exactly that against the real pipeline.
* everything else (embed, lm_head, norm scales) rests in bf16.

Memory budget on a 16 GB v5e: ~3.5 GB int4 payload + ~0.2 GB scales
+ ~2.1 GB bf16 embed+lm_head at rest; decode transiently reconstructs
ONE layer (~0.44 GB bf16 under Policy(param_dtype=bf16)) per scan tick.

The script budgets itself between phases/leaves via PTD_PROBE_BUDGET_S
and exits cleanly when over. The 8b preset refuses to run on CPU (a consumption
metric on the host would be noise wearing a TPU name); --preset tiny is
the CPU rehearsal path and is exercised by tests/test_llama8b.py.
"""

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

t0 = time.time()
BUDGET_S = float(os.environ.get("PTD_PROBE_BUDGET_S", "2400"))


def log(msg):
    print(f"[{time.time() - t0:7.1f}s] {msg}", flush=True)


def over_budget():
    return time.time() - t0 > BUDGET_S


import jax
import jax.numpy as jnp
import numpy as np

import pytorch_distributed_tpu as ptd
from pytorch_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from pytorch_distributed_tpu.ops.quant import (
    quantize_tree_int4,
    quantized_bytes,
)
from pytorch_distributed_tpu.parallel.sharding import path_str
from pytorch_distributed_tpu.runtime.precision import Policy, use_policy

# mirror quantize_for_scan_dequant's gate: only kernels inside the
# scanned stack, judged on the STACKED leaf (that is what the real
# pipeline quantizes)
_INCLUDE = re.compile(r"/block/.*/kernel$")
_MIN_SIZE = 4096


def _quantizable(path: str, sds) -> bool:
    return (
        _INCLUDE.search("/" + path) is not None
        and sds.ndim >= 2
        and sds.size >= _MIN_SIZE
        and sds.shape[-1] % 2 == 0
    )


class BuildBudgetExceeded(RuntimeError):
    """Raised EARLY (after the first leaf's first two layers) when the
    measured per-compile/per-call times project the full build past the
    probe budget minus the decode-compile reserve — so the caller can
    shrink scope while the window is still mostly unspent (the run must
    not die to budget math that was knowable upfront)."""

    def __init__(self, msg, t_compile, t_call, n_quant, layers):
        super().__init__(msg)
        self.t_compile = t_compile
        self.t_call = t_call
        self.n_quant = n_quant
        self.layers = layers


def build_int4_params(
    model, ids0, seed=0, log_fn=lambda m: None, decode_reserve_s=0.0
):
    """The model's params tree in quantize_for_scan_dequant's int4
    layout, built leaf-by-leaf ON DEVICE — peak float transient is one
    LAYER's largest kernel, never the whole tree.

    After the first quantizable leaf's first (compile) and second
    (steady) layer calls, the whole build's cost is projected and
    logged; if it lands past ``BUDGET_S - decode_reserve_s`` the build
    aborts with :class:`BuildBudgetExceeded` carrying the measured
    times, so the caller can retry at a depth the window affords.
    """
    shapes = jax.eval_shape(
        lambda k: model.init(k, ids0), jax.random.key(seed)
    )["params"]
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    n_quant = sum(
        1 for path, sds in flat if _quantizable(path_str(path), sds)
    )
    key = jax.random.key(seed + 1)
    leaves = []
    quant_seen = 0
    for i, (path, sds) in enumerate(flat):
        p = path_str(path)
        key, sub = jax.random.split(key)
        if _quantizable(p, sds):
            quant_seen += 1
            first_quant = quant_seen == 1
            L, per = sds.shape[0], sds.shape[1:]
            fan_in = int(np.prod(per[:-1]))
            std = 1.0 / np.sqrt(fan_in)

            @jax.jit
            def one_layer(k, _per=per, _std=std):
                w = jax.random.normal(k, _per, jnp.float32) * _std
                q = quantize_tree_int4({"w": w}, min_size=1)["w"]
                return q["q4"], q["scale"]

            subkeys = jax.random.split(sub, L)
            q4s, scales = [], []
            for l in range(L):
                if over_budget():
                    raise TimeoutError(
                        f"budget {BUDGET_S:.0f}s spent mid-build "
                        f"(leaf {i}/{len(flat)}, layer {l}/{L})"
                    )
                if first_quant and l <= 1:
                    # time the compile call (l=0) and one steady call
                    # (l=1) synchronously; projection needs real wall
                    # clock, not async-dispatch time
                    t_one = time.perf_counter()
                    a, b = one_layer(subkeys[l])
                    jax.block_until_ready((a, b))
                    t_one = time.perf_counter() - t_one
                    if l == 0:
                        t_compile = t_one
                        # a single-layer leaf never reaches a steady
                        # call — project with t_call=t_compile, an
                        # overestimate, which errs toward aborting
                        t_call = t_compile if L == 1 else None
                    else:
                        t_call = t_one
                    if t_call is not None:
                        # remaining: this leaf's untimed layers + the
                        # other n_quant-1 leaves (compile + L-1 steady
                        # calls each); 1.2x for stacking/non-quant
                        # leaves
                        remaining = 1.2 * (
                            (L - 1 - l) * t_call
                            + (n_quant - 1)
                            * (t_compile + (L - 1) * t_call)
                        )
                        elapsed = time.time() - t0
                        finish = elapsed + remaining
                        ceiling = BUDGET_S - decode_reserve_s
                        log_fn(
                            f"build projection: per-leaf compile "
                            f"{t_compile:.1f}s, per-layer call "
                            f"{t_call * 1e3:.0f}ms x {n_quant} leaves "
                            f"x {L} layers -> finish ~{finish:.0f}s "
                            f"of {ceiling:.0f}s ceiling (budget "
                            f"{BUDGET_S:.0f}s - decode reserve "
                            f"{decode_reserve_s:.0f}s)"
                        )
                        # abort only when the caller declared a decode
                        # reserve — i.e. a timed chip run that must
                        # save window for the decode compile. The tiny
                        # layout pin (reserve 0) logs and carries on.
                        if decode_reserve_s > 0 and finish > ceiling:
                            raise BuildBudgetExceeded(
                                f"projected build finish {finish:.0f}s "
                                f"> ceiling {ceiling:.0f}s",
                                t_compile, t_call, n_quant, L,
                            )
                else:
                    a, b = one_layer(subkeys[l])
                q4s.append(a)
                scales.append(b)
            leaves.append(
                {"q4": jnp.stack(q4s), "scale": jnp.stack(scales)}
            )
            log_fn(
                f"leaf {p}: int4 {sds.shape} -> q4 "
                f"{leaves[-1]['q4'].shape}"
            )
        elif p.endswith("scale"):  # norm scales
            leaves.append(jnp.ones(sds.shape, jnp.bfloat16))
        elif p.endswith("bias"):
            leaves.append(jnp.zeros(sds.shape, jnp.bfloat16))
        else:  # embed / lm_head / unquantized kernels
            fan_in = sds.shape[-2] if sds.ndim >= 2 else sds.shape[-1]
            std = 0.02 if p.endswith("embedding") else 1.0 / np.sqrt(fan_in)
            gen = jax.jit(
                lambda k, _s=sds.shape, _std=std: (
                    jax.random.normal(k, _s, jnp.float32) * _std
                ).astype(jnp.bfloat16)
            )
            leaves.append(gen(sub))
            log_fn(f"leaf {p}: bf16 {sds.shape}")
    return jax.tree_util.tree_unflatten(treedef, leaves)


def check_layout_matches_pipeline(cfg_cls, model_cls, log_fn=lambda m: None):
    """Tiny-model pin: the on-device builder's tree must be structurally
    identical (paths, shapes, dtypes) to init + quantize_for_scan_dequant
    — the layout contract that makes the 8b run representative."""
    from pytorch_distributed_tpu.ops.quant import quantize_for_scan_dequant

    cfg = cfg_cls.tiny()
    cfg = __import__("dataclasses").replace(cfg, scan_dequant=True)
    model = model_cls(cfg)
    ids0 = jnp.zeros((1, 8), jnp.int32)
    built = build_int4_params(model, ids0, log_fn=log_fn)
    ref_params = model.init(jax.random.key(0), ids0)["params"]
    ref = quantize_for_scan_dequant(ref_params, "int4")

    def _quantized_leaf(tree, path):
        # structural test: a leaf belongs to a quantized kernel iff its
        # parent dict carries the sibling "q4" payload — never inferred
        # from the path suffix + dtype, which would silence a real
        # dtype drift in the quantizer's per-channel scales (ADVICE r4)
        node = tree
        for k in path[:-1]:
            node = node[k.key] if hasattr(k, "key") else node[k.idx]
        return isinstance(node, dict) and "q4" in node

    b_flat = jax.tree_util.tree_flatten_with_path(built)[0]
    r_flat = jax.tree_util.tree_flatten_with_path(ref)[0]
    assert len(b_flat) == len(r_flat), (len(b_flat), len(r_flat))
    for (bp, bl), (rp, rl) in zip(b_flat, r_flat):
        assert bp == rp, (bp, rp)
        assert bl.shape == rl.shape, (path_str(bp), bl.shape, rl.shape)
        # quantized payloads AND their per-channel scales must match the
        # pipeline's dtypes exactly; full-precision leaves (incl. norm
        # scales) rest in bf16 here vs the init tree's f32 (the at-rest
        # choice, not a layout difference)
        if _quantized_leaf(built, bp):
            assert bl.dtype == rl.dtype, (path_str(bp), bl.dtype, rl.dtype)
    return built, model, cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=("8b", "tiny"), default="8b")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--batch", type=int, default=1)
    args = ap.parse_args()

    ptd.enable_compilation_cache()
    ptd.init_process_group()
    on_tpu = ptd.is_tpu()
    log(f"platform={ptd.platform()} preset={args.preset}")

    if args.preset == "8b" and not on_tpu:
        log(
            "8b preset needs the real chip (an 8B CPU decode is noise "
            "wearing a TPU metric name) — nothing to do"
        )
        return

    log("layout pin: builder tree == init+quantize_for_scan_dequant tree")
    built_tiny, tiny_model, tiny_cfg = check_layout_matches_pipeline(
        LlamaConfig, LlamaForCausalLM, log_fn=log
    )
    log("layout pin OK")

    depth_note = ""
    if args.preset == "tiny":
        cfg, model, params = tiny_cfg, tiny_model, built_tiny
        B, P, NEW = 2, 8, 8
        iters = 2
    else:
        import dataclasses

        reserve = float(os.environ.get("PTD_DECODE_RESERVE_S", "1200"))
        cfg = dataclasses.replace(
            LlamaConfig.llama3_8b(), scan_dequant=True
        )
        model = LlamaForCausalLM(cfg)
        B, P, NEW = args.batch, args.prompt_len, args.new_tokens
        iters = 3
        log("building 8B int4 tree on device, layer by layer...")
        try:
            params = build_int4_params(
                model, jnp.zeros((1, 8), jnp.int32), log_fn=log,
                decode_reserve_s=reserve,
            )
        except TimeoutError as e:
            log(f"budget spent mid-build ({e}) — stopping")
            return
        except BuildBudgetExceeded as e:
            # the window can't afford 32 layers — take the depth it CAN
            # afford rather than dying mid-build with no executed fact.
            # Same per-layer shapes -> the already-paid compile is
            # reused; only the layer loop shrinks.
            spendable = BUDGET_S - reserve - (time.time() - t0)
            per_leaf_fixed = e.n_quant * e.t_compile
            l_ok = int(
                (spendable / 1.2 - per_leaf_fixed)
                / max(e.n_quant * e.t_call, 1e-9)
            )
            l_ok = max(1, min(cfg.num_layers, l_ok))
            log(
                f"REDUCED DEPTH: full 32-layer build projected past the "
                f"window (compile {e.t_compile:.1f}s/leaf, call "
                f"{e.t_call * 1e3:.0f}ms/layer) — rebuilding at "
                f"num_layers={l_ok}; the metric will say so"
            )
            depth_note = f"_{l_ok}layers"
            cfg = dataclasses.replace(cfg, num_layers=l_ok)
            model = LlamaForCausalLM(cfg)
            try:
                params = build_int4_params(
                    model, jnp.zeros((1, 8), jnp.int32), log_fn=log,
                    decode_reserve_s=reserve,
                )
            except (BuildBudgetExceeded, TimeoutError) as e2:
                log(
                    f"even the reduced-depth build could not finish in "
                    f"the window ({e2}) — stopping with projection-only "
                    f"evidence"
                )
                return

    at_rest = quantized_bytes(params)
    log(f"params at rest: {at_rest / 1e9:.2f} GB")

    rng = np.random.default_rng(0)
    ids = jnp.asarray(
        rng.integers(cfg.vocab_size, size=(B, P)).astype(np.int32)
    )

    serving = Policy(
        param_dtype=jnp.bfloat16,
        compute_dtype=jnp.bfloat16,
        output_dtype=jnp.float32,
    )
    with use_policy(serving):
        run = jax.jit(
            lambda p, i: ptd.generate(
                model, p, i, max_new_tokens=NEW, temperature=0.0
            )
        )
        log(f"compiling + first decode (B={B} P={P} NEW={NEW})...")
        out = run(params, ids)
        jax.block_until_ready(out)
    log("first decode done")

    if over_budget():
        log(f"budget spent before timing loop — stopping with compile-only"
            f" evidence")
        return

    t = time.perf_counter()
    for _ in range(iters):
        out = run(params, ids)
    int(out[0, -1])
    dt = (time.perf_counter() - t) / iters
    tok_per_sec = B * NEW / dt

    peak = ptd.max_memory_allocated()
    mem_note = ""
    try:
        ma = run.lower(params, ids).compile().memory_analysis()
        mem_note = (
            f" xla: args={ma.argument_size_in_bytes / 1e9:.2f}GB "
            f"temps={ma.temp_size_in_bytes / 1e9:.2f}GB "
            f"out={ma.output_size_in_bytes / 1e9:.2f}GB"
        )
    except Exception as e:
        mem_note = f" (memory_analysis unavailable: {type(e).__name__})"

    rec = {
        "metric": f"llama8b{depth_note}_int4_scan_decode_tokens_per_sec"
        if args.preset == "8b"
        else "llama_tiny_int4_scan_decode_tokens_per_sec",
        "value": round(tok_per_sec, 2),
        "unit": f"tokens/sec incl. prefill, int4+scan_dequant bf16, "
        f"batch={B} prompt={P} new={NEW}, {cfg.num_layers} layers",
        "vs_baseline": None,
        "platform": ptd.platform(),
        "at_rest_gb": round(at_rest / 1e9, 3),
        "hbm_peak_gb": round(peak / 1e9, 3) if peak else None,
    }
    print(json.dumps(rec), flush=True)
    log(
        f"decode: {tok_per_sec:.2f} tok/s ({dt * 1e3:.0f} ms/call), "
        f"at-rest {at_rest / 1e9:.2f} GB, peak HBM "
        f"{peak / 1e9:.2f} GB{mem_note}"
    )


if __name__ == "__main__":
    main()
