#!/usr/bin/env python3
"""The expert layer's grouped product on the chip: ``ops.moe.expert_gmm``
(the Pallas kernel the layer keeps) against ``jax.lax.ragged_dot`` on
the same sorted rows, at a decode tick's and a prefill chunk's shapes of
the GigaChat3.1 cut (16 experts held of 256, 8 a token, 7168 -> 2048 ->
7168, bfloat16, uniform random routing).

    python scripts/expert_gmm_bench.py            # on the chip

One JSON line a shape: milliseconds a call of one expert layer's three
products, median of 20, each ending in ``block_until_ready``; and how
far the two disagree. PERF.md records what a v5e read."""

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.ops import moe

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU here: a CPU run measures nothing")
    E, held, K, D, F = 256, 16, 8, 7168, 2048
    kw, kg, ko = jax.random.split(jax.random.key(0), 3)
    w_in = 0.02 * jax.random.normal(kw, (held, D, F), jnp.bfloat16)
    w_gate = 0.02 * jax.random.normal(kg, (held, D, F), jnp.bfloat16)
    w_out = 0.02 * jax.random.normal(ko, (held, F, D), jnp.bfloat16)

    weights = (w_in, w_gate, w_out)

    def act(h, g):
        return jax.nn.silu(g) * h

    # the weights ride as arguments: closed over, they would be baked
    # into each executable as 2.6 GB of constants
    @jax.jit
    def kernel(x, experts, w_in, w_gate, w_out):
        T = x.shape[0]
        local = jnp.where(experts < held, experts, held).reshape(-1)
        tm = moe.row_tile(T * K)
        pair_of_row, _, te, nt, sizes = moe.sorted_dispatch(local, held, tm)
        rows = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])[
            jnp.where(pair_of_row < T * K, pair_of_row // K, T)]
        h = moe.expert_gmm(rows, w_in, te, nt, tm)
        g = moe.expert_gmm(rows, w_gate, te, nt, tm)
        return moe.expert_gmm(act(h, g), w_out, te, nt, tm), pair_of_row

    @jax.jit
    def ragged(x, experts, w_in, w_gate, w_out):
        T = x.shape[0]
        local = jnp.where(experts < held, experts, held).reshape(-1)
        order = jnp.argsort(local, stable=True)
        sizes = jnp.zeros(held + 1, jnp.int32).at[local].add(1)[:held]
        rows = x[order // K]                       # [T * K, D], sorted
        h = jax.lax.ragged_dot(rows, w_in, sizes)
        g = jax.lax.ragged_dot(rows, w_gate, sizes)
        return jax.lax.ragged_dot(act(h, g), w_out, sizes), order, sizes

    for name, T in (("tick", 128), ("chunk", 512)):
        kx, ke = jax.random.split(jax.random.key(T))
        x = jax.random.normal(kx, (T, D), jnp.bfloat16)
        experts = jax.random.randint(ke, (T, K), 0, E)
        out = {"shape": name, "tokens": T,
               "pairs_here": int((experts < held).sum())}
        for label, fn in (("expert_gmm_ms", kernel), ("ragged_dot_ms", ragged)):
            jax.block_until_ready(fn(x, experts, *weights))
            times = []
            for _ in range(20):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(x, experts, *weights))
                times.append((time.perf_counter() - t0) * 1e3)
            out[label] = statistics.median(times)
        # the same pairs, row by row
        yk, pair_of_row = kernel(x, experts, *weights)
        yr, order, sizes = ragged(x, experts, *weights)
        n = int(sizes.sum())
        a = jnp.zeros((T * K + 1, D), jnp.float32).at[
            jnp.where(pair_of_row < T * K, pair_of_row, T * K)
        ].set(yk.astype(jnp.float32))[:T * K]
        b = jnp.zeros((T * K, D), jnp.float32).at[order[:n]].set(
            yr[:n].astype(jnp.float32))
        out["max_abs_difference"] = float(jnp.max(jnp.abs(a - b)))
        out["output_scale"] = float(jnp.max(jnp.abs(b)))
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
