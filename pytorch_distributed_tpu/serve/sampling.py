"""Per-row sampling for heterogeneous slot batches.

``generation.sample_logits`` takes ONE static (temperature, top_k,
top_p) per call — correct for offline batches where every row shares
the sampling config, impossible for a slot batch where every row is a
different request. This module is the row-vectorized form: parameters
arrive as ``[B]`` arrays and every row follows exactly the math of
``generation.filter_logits``/``sample_logits`` with that row's values,
so a request's token stream is BIT-IDENTICAL to a solo ``generate``
call with the same seed and params (pinned by tests/test_serve.py).

How much of this runs is decided BY THE PROGRAM, from the per-row
parameters it is handed (``sample_logits_rows``; one ``lax.cond``, no
static argument, one compiled program): a batch in which no LIVE row
samples takes ``argmax`` and nothing else — no divide, no vocabulary
sort, no softmax, no ``categorical``; a live row that samples buys the
whole filter path below for the batch, filtered or not (an "off"
filter masks nothing). Which branch ran never changes a token, by the
notes below.

Exactness notes (why the filter path is a no-op for "off" rows, bit
for bit):

* ``top_k`` off is encoded as ``k = V``: the k-th sorted logit is the
  row minimum, and ``logits < min`` masks nothing.
* ``top_p`` off is encoded as ``inf``: every sorted entry survives
  ``cum_before < inf``, the surviving minimum is the global minimum,
  and ``logits < min`` again masks nothing. (Encoding "off" as 1.0
  would be *almost* right — but an f32 cumsum can overshoot 1.0 and
  drop a tail token a None-filtered ``generate`` would keep.)
* Filters only MASK (set ``-inf``); kept logits are never rewritten,
  so a no-op mask leaves the row bitwise equal to the unfiltered path.
* Rows are independent: nothing in the filter or the draw crosses
  rows, so a row's token does not depend on which branch the OTHER
  rows put the batch on.
* Greedy rows (``temperature == 0``) take ``argmax`` of the RAW logits
  exactly like ``sample_logits``'s early return; their lane through
  the sampling path (when another row asks for it) divides by a
  substituted 1.0 (never 0) and the result is discarded by the final
  select.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: per-row encodings of "filter off" — see module docstring
TOP_K_OFF = 0
TOP_P_OFF = jnp.inf


def filter_logits_rows(
    logits: jnp.ndarray,   # [B, V]
    temps: jnp.ndarray,    # [B] f32; rows with 0 are greedy (caller selects)
    top_ks: jnp.ndarray,   # [B] int32; TOP_K_OFF (0) = no k filter
    top_ps: jnp.ndarray,   # [B] f32; TOP_P_OFF (inf) = no p filter
) -> jnp.ndarray:
    """Row-wise ``generation.filter_logits``: scale, k-filter, p-filter."""
    V = logits.shape[-1]
    neg_inf = jnp.finfo(jnp.float32).min
    safe_t = jnp.where(temps > 0, temps, 1.0).astype(jnp.float32)
    l32 = logits.astype(jnp.float32) / safe_t[:, None]
    # one descending sort serves both filters (generation.filter_logits)
    sorted_desc = jnp.sort(l32, axis=-1)[..., ::-1]
    k = jnp.where(top_ks > 0, jnp.minimum(top_ks, V), V).astype(jnp.int32)
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    l32 = jnp.where(l32 < kth, neg_inf, l32)
    sorted_desc = jnp.where(
        jnp.arange(V)[None, :] < k[:, None], sorted_desc, neg_inf
    )
    # a token survives if the cumulative probability BEFORE it is still
    # < top_p (the top token always survives)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum_before = jnp.cumsum(probs, axis=-1) - probs
    keep = cum_before < top_ps[:, None]
    thresh = jnp.min(
        jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(l32 < thresh, neg_inf, l32)


def sample_logits_rows(
    logits: jnp.ndarray,    # [B, V]
    subkeys,                # [B] typed rng keys (one consumed per row)
    temps: jnp.ndarray,
    top_ks: jnp.ndarray,
    top_ps: jnp.ndarray,
    live: jnp.ndarray | None = None,  # [B] bool; None = every row
) -> jnp.ndarray:
    """[B, V] logits -> [B] token ids, each row by its own params/key.

    Greedy rows (``temps == 0``) are ``argmax`` of the raw logits;
    sampling rows draw ``categorical`` from their filtered/scaled
    distribution with their own key — the exact per-row transcript of
    ``generation.sample_logits``.

    ``live`` marks the rows whose token anyone reads. It only prices
    the call (module docstring): a row that is not live — a freed slot
    still carrying its last request's temperature — never sends the
    batch down the sampling branch, and its own lane holds whatever
    the branch the live rows chose computes for it.
    """
    sampling = temps > 0
    if live is not None:
        sampling = sampling & live
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled():
        filtered = filter_logits_rows(logits, temps, top_ks, top_ps)
        return jax.vmap(
            lambda key, row: jax.random.categorical(key, row, axis=-1)
        )(subkeys, filtered).astype(jnp.int32)

    nxt = jax.lax.cond(jnp.any(sampling), sampled, lambda: greedy)
    return jnp.where(temps <= 0, greedy, nxt)
