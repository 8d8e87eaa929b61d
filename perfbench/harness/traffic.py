"""One general generator of request traffic, read from a mix's file.

A mix (``perfbench/traffic/<mix>.json``) states distributions and
arrivals; the generator turns it and ``--seed`` into requests. Every
seed gets the SAME multiset of (prompt length, output length, shared or
not, arrival gap): the lengths are the quantiles of the stated
distributions, in blocks of ``block`` requests, paired by a permutation
fixed in the file, so any stretch of ``block`` requests carries the whole
distribution. The seed draws the token ids (and the weights, elsewhere).
With ``"order": "shuffled"`` it also orders the requests inside each
block; with ``"order": "fixed"`` the order is the file's own, the same
for every seed: where a window sees only a block or two, which requests
happen to fall into it is most of the spread between seeds, and a fixed
order offers every seed the same work at the same moments.
"""

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def quantiles(dist, n):
    """``n`` whole-number lengths at the mid-quantiles of ``dist``."""
    qs = [(i + 0.5) / n for i in range(n)]
    if dist["dist"] == "lognormal":
        mu, sigma = math.log(dist["median"]), dist["sigma"]
        vals = [math.exp(mu + sigma * _NORMAL.inv_cdf(q)) for q in qs]
    elif dist["dist"] == "uniform":
        vals = [dist["min"] + q * (dist["max"] - dist["min"]) for q in qs]
    elif dist["dist"] == "fixed":
        vals = [dist["value"]] * n
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    lo, hi = dist.get("min", 1), dist.get("max", float("inf"))
    return [int(round(min(max(v, lo), hi))) for v in vals]


def block_pattern(mix):
    """The fixed block: ``block`` tuples (prompt_len, output_len,
    prefix index or -1, arrival gap in units of the mean gap)."""
    B = mix["block"]
    fixed = np.random.default_rng(mix.get("pairing_seed", 0))
    prompts = quantiles(mix["prompt_len"], B)
    outputs = [quantiles(mix["output_len"], B)[i]
               for i in fixed.permutation(B)]
    sp = mix.get("shared_prefix")
    prefix = [-1] * B
    if sp:
        chosen = fixed.permutation(B)[: int(round(sp["share"] * B))]
        for j, i in enumerate(sorted(chosen)):
            prefix[i] = j % sp["prompts"]
            # a shared request holds the system prompt and a tail
            prompts[i] = max(prompts[i], sp["tokens"] + sp["min_tail"])
    if mix["arrivals"]["process"] == "poisson":
        # exponential gaps at their mid-quantiles: mean 1 over the block
        gaps = [-math.log(1.0 - (i + 0.5) / B) for i in range(B)]
        norm = sum(gaps) / B
        gaps = [g / norm for g in gaps]
        gaps = [gaps[i] for i in fixed.permutation(B)]
    else:
        gaps = [0.0] * B
    return list(zip(prompts, outputs, prefix, gaps))


def generate(mix, seed, vocab_size, *, horizon_s):
    """``(requests, due)``: dicts with ``prompt_ids``, ``max_new_tokens``,
    ``prefix`` and the seconds from the start at which each is due.
    ``horizon_s`` bounds an open-loop mix; a backlog has ``requests``."""
    rng = np.random.default_rng(seed)
    pattern = block_pattern(mix)
    B = mix["block"]
    arr = mix["arrivals"]
    if arr["process"] == "poisson":
        n = int(math.ceil(arr["rate_per_s"] * horizon_s / B)) * B
        mean_gap = 1.0 / arr["rate_per_s"]
    elif arr["process"] == "backlog":
        n = int(math.ceil(mix["requests"] / B)) * B
        mean_gap = 0.0
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    sp = mix.get("shared_prefix")
    systems = [
        rng.integers(1, vocab_size, size=sp["tokens"]).astype(np.int32)
        for _ in range(sp["prompts"])
    ] if sp else []
    reqs, due, t = [], [], 0.0
    order = mix.get("order", "shuffled")
    if order not in ("fixed", "shuffled"):
        raise ValueError(f"unknown order {order!r}")
    fixed = np.random.default_rng(mix.get("pairing_seed", 0) + 1)
    for _ in range(n // B):
        inside = (fixed if order == "fixed" else rng).permutation(B)
        for i in inside:
            p_len, o_len, pref, gap = pattern[i]
            t += gap * mean_gap
            if pref >= 0:
                tail = rng.integers(
                    1, vocab_size, size=p_len - sp["tokens"]
                ).astype(np.int32)
                ids = np.concatenate([systems[pref], tail])
            else:
                ids = rng.integers(1, vocab_size, size=p_len).astype(np.int32)
            reqs.append({"prompt_ids": ids, "max_new_tokens": o_len,
                         "prefix": pref})
            due.append(t)
    return reqs, due


def describe(mix, reqs, due):
    """One line on what was drawn."""
    p = sorted(len(r["prompt_ids"]) for r in reqs)
    o = sorted(r["max_new_tokens"] for r in reqs)
    q = lambda v, f: v[min(int(f * len(v)), len(v) - 1)]  # noqa: E731
    shared = sum(r["prefix"] >= 0 for r in reqs)
    arr = mix["arrivals"]
    rate = arr.get("rate_per_s", 0.0)
    return (
        f"traffic: {len(reqs)} requests, arrivals {arr['process']}"
        f" rate {rate}/s over {due[-1]:.1f}s; prompts min/p50/p95/max "
        f"{p[0]}/{q(p, .5)}/{q(p, .95)}/{p[-1]} mean {sum(p) / len(p):.0f};"
        f" outputs {o[0]}/{q(o, .5)}/{q(o, .95)}/{o[-1]} mean "
        f"{sum(o) / len(o):.0f}; {shared} open with a shared prefix"
    )
