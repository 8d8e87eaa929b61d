"""Paged, prefix-shared KV-cache pool — the serving engine's memory system.

The original pool gave every request a monolithic ``[max_len]`` slot:
simple, but each slot pinned ``max_len - actual_len`` dead positions of
HBM forever — fatal at a realistic length mix, where the p50 request is
a fraction of the p99 the pool must be sized for. This rewrite makes the
PAGE the allocation unit:

* **Device storage is one page pool per KV leaf**:
  ``[..., num_pages + 1, page_size, H * D]`` (page 0 is a reserved null
  page — never allocated, padding for unused page-table entries; a
  scanned model's leading ``[L]`` is part of the same leaf). A frame is
  lane-dense — heads folded into the minor dimension — which is the
  layout the paged-attention kernel reads in place (ops/paged_attention
  says what the chip does with any other). Pages are position-agnostic
  frames; which request owns which page, at which sequence offset, is
  host bookkeeping.
* **Requests hold a page table** (``[max_pages]`` int32 per slot) instead
  of a buffer row. The jitted programs gather a request's pages into a
  dense ``[max_len]`` view, run the unchanged model decode contract
  (``write_pos`` per-row writes, per-row causal masks), and scatter ONLY
  the deliberately-written positions back. The persistent pool is
  written by nothing else — free slots and mid-prefill rows no longer
  even write garbage (their scatter indices are dropped), which is a
  strictly stronger invariant than the old "garbage lands where masks
  hide it".
* **Freed pages return to one shared free list** (a min-heap: lowest
  page first, so seeded workloads replay exactly; push/pop is O(log n)
  with tiny constants — measured flat from 64 to 2048 slots in the
  serving bench's admit micro-pin, vs the old allocate's per-call sort).
* **Identical prefixes share pages copy-free via refcounts.** Full
  prompt pages are content-addressed by a chain hash of the token
  prefix; admission walks the registry and maps matching leading pages
  into the new request's table (refcount++, zero bytes copied, zero
  prefill compute), resuming prefill at the first unshared page.
  Copy-on-write discipline is enforced eagerly at admission: a shared
  page is READ-ONLY — the page containing the first divergent (or
  to-be-written) token is always private, so no jitted program can ever
  write a refcount>1 page. The partial boundary page is recomputed by
  the request's own prefill rather than copied (identical bytes either
  way — KV at position p depends only on tokens [0, p]).

Bit-parity story (why sharing cannot change tokens): a shared page holds
exactly the KV this request's own prefill would have produced — same
tokens, same absolute positions, same deterministic program — so the
gathered dense view is bitwise what the unshared engine computed, and
the solo-``generate`` parity suite holds with sharing on.

Where a dense view is still made: the decode tick, the speculative
verify and a prompt chunk attend IN PLACE over the pool
(``ops/paged_attention``) — new K/V lands via per-page scatters and
attention reads the pages. The gather helpers below (``gather_pages`` /
``scatter_kv``) serve the one dense span that remains, the speculative
draft's short context, bucket-sliced to the live maximum's
power-of-two page width, never ``max_len``. Resident KV is
``pages_in_use × page_size`` (``serving_kv_bytes_ratio`` >= 2x pinned by
test_bench_contract).
"""

from __future__ import annotations

import dataclasses
import hashlib
import heapq
import math
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_tpu.generation import cache_batch_axis
from pytorch_distributed_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# warn-once dedup for degenerate auto page sizes (the rule-engine's
# replicate-with-warning precedent, autoplan/rules.py)
_warned_page_sizes: set = set()


def reset_page_size_warnings() -> None:
    """Clear the warn-once dedup (tests asserting the warning fires)."""
    _warned_page_sizes.clear()


def auto_page_size(max_len: int, cap: int = 32) -> int:
    """Largest power-of-two divisor of ``max_len``, capped at ``cap``.

    A page must divide ``max_len`` exactly (the dense view is
    ``max_pages * page_size`` wide and the engine equates it with
    ``max_len``); powers of two keep the div/mod in the scatter index
    arithmetic cheap. ``max_len`` odd degenerates to 1-token pages —
    still VALID, but every token becomes its own page: the page table
    is ``max_len`` entries per slot, every allocation/refcount walk is
    per-token, the paged-attention stream pays one page step per
    token, and prefix sharing hashes per token. That cost used to be
    silent; now it warns once per ``max_len`` (the rule engine's
    replicate-with-warning precedent) — pass an even/power-of-two
    ``max_len`` or an explicit ``page_size`` to opt out knowingly.
    """
    ps = math.gcd(max_len, 1 << 30)  # largest power-of-2 divisor
    while ps > cap:
        ps //= 2
    if ps == 1 and max_len > 1 and max_len not in _warned_page_sizes:
        _warned_page_sizes.add(max_len)
        logger.warning(
            "auto_page_size(max_len=%d): odd max_len degenerates to "
            "1-token pages — %d page-table entries per slot, per-token "
            "bookkeeping and page streaming, per-token prefix hashing. "
            "Use an even (ideally power-of-two-divisible) max_len or "
            "pass page_size explicitly.",
            max_len, max_len,
        )
    return ps


def page_axis(path, leaf) -> Optional[int]:
    """Page axis of a POOL leaf, or None for shared counters: KV
    payloads, their int8 scales and a latent cache's one leaf are
    ``[..., P + 1, page_size, F]`` (a leading ``[L]`` when layers are
    scanned; a model may hold stacked and unstacked leaves side by
    side), so it is ``ndim - 3``; counters are rank 0 (``[L]`` when
    scanned). The pool's counterpart of ``generation.cache_batch_axis``,
    which speaks for the DENSE ``[..., B, T, H, D]`` views; both tell a
    leaf by its geometry, not its name."""
    del path
    return leaf.ndim - 3 if leaf.ndim >= 3 else None


def _page_cache(model, params, num_pages: int, page_size: int):
    """(zeroed pool pytree, ``{KV leaf path: (H, D)}``) from ONE
    abstract trace of the model's own decode apply (batch = page
    frames, length = page size). The second is what a frame's folded
    minor dimension unfolds to in the model's dense cache — the one
    thing :func:`gather_pages` cannot read off a pool."""

    def shape_fn(p):
        _, state = model.apply(
            {"params": p},
            jnp.zeros((num_pages + 1, 1), jnp.int32),
            decode=True,
            cache_len=page_size,
            mutable=["cache"],
        )
        return state["cache"]

    tails = {}

    def f(path, s):
        shape = s.shape
        if cache_batch_axis(path, s) is not None:
            tails[jax.tree_util.keystr(path)] = shape[-2:]
            shape = shape[:-2] + (shape[-2] * shape[-1],)
        return jnp.zeros(shape, s.dtype)

    cache = jax.tree_util.tree_map_with_path(
        f, jax.eval_shape(shape_fn, params)
    )
    return cache, tails


def init_page_cache(model, params, num_pages: int, page_size: int):
    """Zeroed page-pool pytree: ``num_pages + 1`` frames of ``page_size``.

    Shapes come from ``jax.eval_shape`` over the model's own decode
    apply (batch = page frames, length = page size), so the pool is
    EXACTLY the leaf set the model mutates — scan layouts, int8 KV
    scale buffers and all — reinterpreted as position-agnostic frames,
    each KV leaf's ``[H, D]`` tail folded into one lane-dense ``[H * D]``.
    Frame 0 is the reserved null page backing unused page-table entries.
    """
    return _page_cache(model, params, num_pages, page_size)[0]


def _lead_index(leaf, ax: int):
    """Index arrays over a pool leaf's axes before its page axis (a
    scanned model's ``[L]``), each broadcasting against a trailing
    ``[N]`` of frames. Gathers and scatters of frames INDEX those axes
    and never slice them: with a window that spans the layers the
    chip's compiler re-lays the whole leaf out layer-minor around a
    scatter (and back), and splits it in two copies ahead of a gather."""
    return tuple(
        jnp.arange(n).reshape((n,) + (1,) * (ax - i))
        for i, n in enumerate(leaf.shape[:ax])
    )


def gather_pages(cache, page_tables: jnp.ndarray, tails):
    """Pool pytree + ``[B, max_pages]`` tables -> dense ``[B, T]`` view.

    ``T = max_pages * page_size``; ``tails`` is the pool's
    ``PagedKVPool.tails``. Only KV-payload leaves (:func:`page_axis` —
    int8 scale buffers included) are gathered; shared counters pass
    through untouched. The result is a valid decode cache for
    ``model.apply`` with per-row ``write_pos``/``positions``. One
    gather along the leaf's own page axis: what it reads and writes is
    as wide as the tables, not the pool.
    """
    B, mp = page_tables.shape
    flat = page_tables.reshape(-1)

    def f(path, x):
        ax = page_axis(path, x)
        if ax is None:
            return x
        ps = x.shape[ax + 1]
        g = x[_lead_index(x, ax) + (flat,)]     # [.., B * mp, ps, F]
        return g.reshape(
            x.shape[:ax] + (B, mp * ps) + tails[jax.tree_util.keystr(path)]
        )

    return jax.tree_util.tree_map_with_path(f, cache)


def scatter_kv(cache, dense, page_tables, positions, keep):
    """Write ``positions`` of the dense view back into the page pool.

    ``positions``/``keep`` are ``[B, W]``: for each dense row, the W
    buffer positions whose KV should persist, and a bool gate per
    position (False -> the write is DROPPED, not redirected — the one
    mechanism that keeps free/mid-prefill rows from ever touching the
    pool). Every kept position must land in a page the row privately
    owns — the pool's copy-on-write discipline guarantees it at
    admission, and ``PagedKVPool.check_consistency`` + the shared-page
    checksum test pin it.

    The caller is the engine's jitted speculative tick only (its draft;
    a prompt chunk and every tick write through
    ``ops.paged_attention.paged_write``). ONE scatter per leaf along the
    leaf's own page and row axes, the leaf where it lies: the compiled
    program writes ``B * W`` positions into the donated pool and reads
    or moves nothing else of it. (Moving the page axis to the front
    for the scatter is, on the chip, a transpose of the whole pool each
    way.)
    """
    B, W = positions.shape

    def f(path, x, d):
        ax = page_axis(path, x)
        if ax is None:
            return x
        npp, ps = x.shape[ax], x.shape[ax + 1]
        # page-table rows are per dense row; positions beyond the table
        # clamp (jnp.take_along_axis default) — such rows are always
        # keep=False so the clamped garbage index is dropped anyway
        page = jnp.take_along_axis(page_tables, positions // ps, axis=1)
        page = jnp.where(keep, page, npp)                   # OOB -> drop
        idx = positions.reshape((1,) * ax + (B, W, 1, 1))
        upd = jnp.take_along_axis(d, idx, axis=ax + 1)      # [.., B, W, H, D]
        upd = upd.reshape(x.shape[:ax] + (B * W, x.shape[-1]))
        at = _lead_index(x, ax) + (
            page.reshape(-1), (positions % ps).reshape(-1),
        )
        return x.at[at].set(  # ptdlint: disable=PTD004
            upd.astype(x.dtype), mode="drop",
        )  # fused scatter: only ever traced inside the engine's jitted
        # programs (cross-module, so the per-module lint closure cannot
        # see the jit wrapping it)

    return jax.tree_util.tree_map_with_path(f, cache, dense)


def _frame_leaves(cache):
    """(name, batch_axis, leaf) for every KV-payload leaf, in canonical
    tree-flatten order — the ONE iteration order the frame codec (and
    therefore the migration wire format and the prefix store's page
    payloads) is defined over."""
    out = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        ax = page_axis(path, leaf)
        if ax is not None:
            name = getattr(path[-1], "key", None) or str(path[-1])
            out.append((name, ax, leaf))
    return out


def frame_signature(cache, page_size: int) -> str:
    """Geometry commitment for one page frame: leaf names, per-frame
    shapes and dtypes (in codec order) plus the page size. Two pools
    agree on this string iff ``extract_frames`` bytes from one splice
    losslessly into the other — the DETAIL string the migration
    fingerprint handshake commits to (the ``_verify_p2p`` idiom)."""
    parts = [f"ps={page_size}"]
    for name, ax, leaf in _frame_leaves(cache):
        frame = leaf.shape[:ax] + leaf.shape[ax + 1:]
        parts.append(f"{name}:{frame}:{leaf.dtype}")
    return "|".join(parts)


def frame_nbytes(cache) -> int:
    """Native bytes of ONE page frame across the KV-payload leaves —
    the exact per-page payload size ``extract_frames`` produces (int8
    caches: int8 K/V plus their f32 per-token scale sidecars)."""
    total = 0
    for _, ax, leaf in _frame_leaves(cache):
        elems = leaf.size // leaf.shape[ax]
        total += int(elems) * leaf.dtype.itemsize
    return total


def token_nbytes(cache) -> int:
    """Bytes of ONE token in the widest KV-payload leaf of one layer —
    what the paged-attention kernel sizes its blocks by
    (``ops.paged_attention.block_pages``)."""
    return max(
        leaf.shape[-1] * leaf.dtype.itemsize
        for _, _, leaf in _frame_leaves(cache)
    )


def kv_frame_width(cache) -> Optional[int]:
    """Elements of ONE token in a K leaf of a K/V pool (``Hkv * D``);
    None for a latent pool (``cached_latent``: one frame a token, no
    heads of its own), whose prompt chunks gather their bucket."""
    leaves = {name: leaf for name, _, leaf in _frame_leaves(cache)}
    if "cached_latent" in leaves:
        return None
    return leaves["cached_key"].shape[-1]


def frame_f32_nbytes(cache) -> int:
    """Bytes ONE page frame would cost with an f32 KV cache: payload
    elements at 4 bytes, no scale sidecars (an f32 cache has none).
    The denominator of the bench's migration-bytes ratio — an int8
    pool's native frames cost ``(1 + 4/D) / 4`` of this."""
    total = 0
    for name, ax, leaf in _frame_leaves(cache):
        if name.endswith("_scale"):
            continue
        total += int(leaf.size // leaf.shape[ax]) * 4
    return total


def extract_frames(cache, pages) -> np.ndarray:
    """Gather whole page frames into one flat ``uint8`` payload.

    Layout is leaf-major in ``_frame_leaves`` order: for each KV-payload
    leaf, the ``len(pages)`` frames' native bytes (C order, native
    dtype — int8 payloads ship as int8, their scale sidecars as f32).
    Verbatim bytes, so a splice on a geometry-identical pool is
    lossless for ANY cache dtype: migration can never change tokens.
    """
    idx = jnp.asarray(np.asarray(pages, np.int32).reshape(-1))
    chunks = []
    for _, ax, leaf in _frame_leaves(cache):
        g = np.asarray(jnp.take(leaf, idx, axis=ax))
        chunks.append(np.ascontiguousarray(g).tobytes())
    return np.frombuffer(b"".join(chunks), np.uint8)


def splice_frames(cache, pages, payload):
    """Inverse of :func:`extract_frames`: write frame bytes into the
    pool at ``pages``. Host-side, once per migrated request (NOT per
    tick — the per-request cost the eager-scatter rule polices is paid
    exactly once per hand-off, priced in the bench's migration
    accounting). Raises when the payload size disagrees with the pool's
    frame geometry — the byte-level half of the fingerprint handshake.
    """
    idx = jnp.asarray(np.asarray(pages, np.int32).reshape(-1))
    n = int(idx.size)
    buf = np.asarray(payload, np.uint8).reshape(-1)
    off = 0

    def f(path, leaf):
        nonlocal off
        ax = page_axis(path, leaf)
        if ax is None:
            return leaf
        shape = leaf.shape[:ax] + (n,) + leaf.shape[ax + 1:]
        count = int(np.prod(shape, dtype=np.int64)) * leaf.dtype.itemsize
        if off + count > buf.size:
            raise ValueError(
                f"migration payload too short: leaf at {path} needs "
                f"bytes [{off}, {off + count}) of {buf.size}"
            )
        frames = np.ascontiguousarray(buf[off:off + count]).view(
            leaf.dtype
        ).reshape(shape)
        off += count
        at = (slice(None),) * ax + (idx,)
        return leaf.at[at].set(  # ptdlint: disable=PTD004
            jnp.asarray(frames)
        )  # once per migrated request (bounded, priced), never per tick

    out = jax.tree_util.tree_map_with_path(f, cache)
    if off != buf.size:
        raise ValueError(
            f"migration payload size mismatch: spliced {off} bytes, "
            f"payload holds {buf.size} — pool geometries disagree"
        )
    return out


@dataclasses.dataclass(frozen=True)
class SlotLease:
    """One admission's allocation: which slot, which pages, where
    prefill resumes. ``page_row`` is the device-ready ``[max_pages]``
    table row (unused entries = null page 0); ``page_keys`` are the
    chain-hash keys of the prompt's full pages, kept so the pool can
    register them for future sharing once prefill has written them."""

    slot: int
    skip: int                 # prefill resumes here (page-aligned, < P)
    page_row: np.ndarray      # [max_pages] int32
    n_pages: int              # pages charged to this slot
    shared_pages: int         # leading pages mapped from the registry
    page_keys: Tuple[bytes, ...]


class PagedKVPool:
    """Page-pool device tree + host page tables / refcounts / registry.

    ``lengths[i]`` keeps its old meaning — slot ``i``'s filled dense
    prefix, the single source of truth the engine turns into positions,
    write cursors and the implicit per-row causal mask. What changed is
    what backs a slot: a page table instead of a buffer row.
    """

    def __init__(
        self,
        model,
        params,
        num_slots: int,
        max_len: int,
        *,
        page_size: Optional[int] = None,
        num_pages: Optional[int] = None,
        prefix_cache: bool = True,
    ):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        ps = page_size or auto_page_size(max_len)
        if ps < 1 or max_len % ps:
            raise ValueError(
                f"page_size {ps} must be >= 1 and divide max_len "
                f"{max_len} (the dense view is max_pages * page_size "
                f"wide and must equal max_len exactly)"
            )
        self.num_slots = num_slots
        self.max_len = max_len
        self.page_size = ps
        self.max_pages = max_len // ps
        # default sizes the pool at memory parity with the old fixed
        # [S, max_len] design — callers size it DOWN to the realistic
        # length mix for the memory win (bench.py's serving_paged phase)
        self.num_pages = (
            num_pages if num_pages is not None
            else num_slots * self.max_pages
        )
        if self.num_pages < self.max_pages:
            raise ValueError(
                f"num_pages {self.num_pages} cannot hold even one "
                f"max-length request ({self.max_pages} pages)"
            )
        self.prefix_cache = prefix_cache
        # the pool, and each KV leaf's dense (H, D) for gather_pages
        self.cache, self.tails = _page_cache(
            model, params, self.num_pages, ps
        )
        self.lengths = np.zeros(num_slots, np.int32)
        self.page_tables = np.zeros(
            (num_slots, self.max_pages), np.int32
        )
        self._free_slots: List[int] = list(range(num_slots))
        heapq.heapify(self._free_slots)
        self._occupied = np.zeros(num_slots, bool)
        self._free_pages: List[int] = list(range(1, self.num_pages + 1))
        heapq.heapify(self._free_pages)
        self._ref = np.zeros(self.num_pages + 1, np.int32)
        self._slot_pages: List[Tuple[int, ...]] = [
            () for _ in range(num_slots)
        ]
        # prefix registry: chain-hash key -> page id, LRU-ordered; an
        # entry holds one refcount, so a registered page survives its
        # writer's retirement and stays shareable until evicted
        self._registry: "OrderedDict[bytes, int]" = OrderedDict()
        self._page_key: Dict[int, bytes] = {}
        # observability counters (engine telemetry + loadgen summary)
        self.prefix_lookups = 0
        self.prefix_hits = 0          # admissions that shared >= 1 page
        self.shared_tokens = 0        # prompt tokens served from shares
        self.prompt_tokens = 0
        self.peak_pages = 0

    # -- prefix hashing ----------------------------------------------------
    def chain_keys(self, prompt_ids) -> List[bytes]:
        """Chain hash per FULL prompt page: key_i commits to tokens
        [0, (i+1)*page_size) — prefix identity, not mere page content.

        Exposed so a caller retrying a page-blocked admission every
        engine step can hash the (immutable) prompt ONCE and pass the
        result back via ``keys=`` — the keys depend only on the tokens
        and the page size, so they are shared between the target and
        draft pools (same geometry by construction). Returns [] with
        the prefix cache off."""
        if not self.prefix_cache:
            return []
        ids = np.ascontiguousarray(prompt_ids, dtype=np.int32)
        ps = self.page_size
        keys, key = [], b""
        for i in range(len(ids) // ps):
            h = hashlib.blake2b(key, digest_size=16)
            h.update(ids[i * ps:(i + 1) * ps].tobytes())
            key = h.digest()
            keys.append(key)
        return keys

    # -- allocation --------------------------------------------------------
    def shareable_skip(
        self,
        prompt_ids,
        *,
        max_new: int = 0,
        chunk: Optional[int] = None,
        tail: int = 0,
        max_skip: Optional[int] = None,
        keys: Optional[List[bytes]] = None,
    ) -> int:
        """How many prompt tokens an allocate() now would serve from the
        registry (page-aligned). Read-only — lets a caller coordinating
        two pools (the speculative engine's target + draft) compute the
        joint skip before committing either allocation. ``keys`` must
        be this prompt's ``chain_keys`` when precomputed."""
        plan = self._plan(
            np.asarray(prompt_ids, np.int32).reshape(-1),
            max_new=max_new, chunk=chunk, tail=tail, max_skip=max_skip,
            keys=keys,
        )
        return plan[1] * self.page_size

    def _plan(self, ids, *, max_new, chunk, tail, max_skip, keys=None):
        """(keys, shared_pages, span) for a prospective admission."""
        P = int(ids.size)
        ps = self.page_size
        if keys is None:
            keys = self.chain_keys(ids)
        # at least one real prompt token must prefill (the final chunk
        # samples the first token from the last prompt column)
        cap = (P - 1) // ps
        if max_skip is not None:
            cap = min(cap, max_skip // ps)
        shared = 0
        for i in range(min(len(keys), cap)):
            if keys[i] not in self._registry:
                break
            shared += 1

        def span_for(shared_pages: int) -> int:
            skip = shared_pages * ps
            pre_end = skip + (
                -(-(P - skip) // chunk) * chunk if chunk else P - skip
            )
            return max(P + max_new + tail, pre_end)

        # chunked prefill writes full chunk widths from `skip`; if the
        # (page-aligned, not chunk-aligned) skip pushes the padded final
        # chunk past the dense width, drop shares until it fits
        while shared and span_for(shared) > self.max_len:
            shared -= 1
        span = span_for(shared)
        if span > self.max_len:
            raise ValueError(
                f"request needs {span} buffer positions (prompt {P} "
                f"rounded to chunks of {chunk} + {max_new} new "
                f"+ {tail} speculative) but max_len is {self.max_len}"
            )
        return keys, shared, span

    def allocate(
        self,
        prompt_ids=None,
        *,
        max_new: int = 0,
        chunk: Optional[int] = None,
        tail: int = 0,
        max_skip: Optional[int] = None,
        keys: Optional[List[bytes]] = None,
    ) -> Optional[SlotLease]:
        """Admit one request: lowest free slot + pages for its worst-case
        span, sharing registered prefix pages where the registry allows.
        Returns None when slots or pages are exhausted (the caller keeps
        the request queued — strict FIFO, no admission reordering).

        ``tail`` reserves extra positions past ``prompt + max_new`` (the
        speculative verify writes up to k rejected-draft entries beyond
        the emitted horizon). ``max_skip`` caps prefix sharing (used to
        align the target and draft pools on one joint skip); ``keys``
        passes precomputed ``chain_keys`` so a head-of-line request
        retried every engine step hashes its prompt once, not per
        attempt.
        """
        if not self._free_slots:
            return None
        ps = self.page_size
        ids = (
            np.asarray(prompt_ids, np.int32).reshape(-1)
            if prompt_ids is not None else np.zeros(0, np.int32)
        )
        P = int(ids.size)
        if P:
            keys, shared_n, span = self._plan(
                ids, max_new=max_new, chunk=chunk, tail=tail,
                max_skip=max_skip, keys=keys,
            )
        else:
            keys, shared_n = [], 0
            span = max(max_new + tail, 1)
        n_span = -(-span // ps)
        needed = n_span - shared_n
        # feasibility BEFORE mutation: free pages plus registry entries
        # nothing references (evictable) must cover the private need
        shared_pages = [self._registry[k] for k in keys[:shared_n]]
        evictable = sum(
            1 for pg in self._registry.values()
            if self._ref[pg] == 1 and pg not in shared_pages
        )
        if needed > len(self._free_pages) + evictable:
            return None
        # commit: pin shares first so eviction can never reap them
        for pg in shared_pages:
            self._ref[pg] += 1
            self._registry.move_to_end(self._page_key[pg])
        fresh = []
        for _ in range(needed):
            if not self._free_pages:
                self._evict_lru()
            fresh.append(heapq.heappop(self._free_pages))
        for pg in fresh:
            self._ref[pg] = 1
        slot = heapq.heappop(self._free_slots)
        self._occupied[slot] = True
        row = np.zeros(self.max_pages, np.int32)
        row[:shared_n] = shared_pages
        row[shared_n:n_span] = fresh
        self.page_tables[slot] = row
        self._slot_pages[slot] = tuple(shared_pages) + tuple(fresh)
        skip = shared_n * ps
        self.lengths[slot] = skip
        if P:
            self.prefix_lookups += 1
            self.prompt_tokens += P
            if shared_n:
                self.prefix_hits += 1
                self.shared_tokens += skip
        self.peak_pages = max(self.peak_pages, self.pages_in_use)
        return SlotLease(
            slot=slot, skip=skip, page_row=row, n_pages=n_span,
            shared_pages=shared_n, page_keys=tuple(keys),
        )

    def _evict_lru(self) -> None:
        """Reap the least-recently-shared registry page nobody holds."""
        for key, pg in self._registry.items():
            if self._ref[pg] == 1:
                del self._registry[key]
                del self._page_key[pg]
                self._ref[pg] = 0
                heapq.heappush(self._free_pages, pg)
                return
        raise RuntimeError(
            "page eviction requested with no evictable registry entry "
            "(allocate() counted wrong — a refcount invariant broke)"
        )

    def register_prefix(self, lease: SlotLease, prompt_ids) -> None:
        """Publish a finished prefill's full prompt pages for sharing.

        Called once the slot's prefill completed (every full page now
        holds canonical prompt KV; the padded final-chunk garbage and
        all decode writes land strictly beyond the last full page, so a
        registered page is immutable for the rest of its life). Already-
        registered keys just refresh their LRU position; a racing
        duplicate keeps the first registration canonical.
        """
        if not self.prefix_cache:
            return
        row = self.page_tables[lease.slot]
        for i, key in enumerate(lease.page_keys):
            page = int(row[i])
            cur = self._registry.get(key)
            if cur is not None:
                self._registry.move_to_end(key)
                continue
            if page in self._page_key:  # already canonical for another key
                continue
            self._registry[key] = page
            self._page_key[page] = key
            self._ref[page] += 1

    def adopt_page(self, key: bytes) -> Optional[int]:
        """Claim one free page and register it under ``key`` — the
        bookkeeping half of pulling a prefix page from a cross-engine
        store (``serve/prefix_store.py``): the caller splices the
        store's canonical frame bytes into the returned page, after
        which the page is indistinguishable from one this pool's own
        prefill produced and every sharing invariant applies unchanged.
        The registry holds the page's one reference (it survives any
        requester's retirement, exactly like a locally-registered
        prefix). Returns the already-registered page when ``key`` is
        known, and None when the prefix cache is off or no page can be
        freed — adoption is an optimization, never a requirement."""
        if not self.prefix_cache:
            return None
        cur = self._registry.get(key)
        if cur is not None:
            self._registry.move_to_end(key)
            return cur
        if not self._free_pages:
            if not any(
                self._ref[pg] == 1 for pg in self._registry.values()
            ):
                return None
            self._evict_lru()
        pg = heapq.heappop(self._free_pages)
        self._ref[pg] = 1
        self._registry[key] = pg
        self._page_key[pg] = key
        return pg

    def free(self, slot: int) -> None:
        """Retire a slot: drop its page references; pages nobody else
        holds (no other slot, no registry entry) return to the free
        list. O(pages held); no device writes — unreferenced page bytes
        are dead until reallocation overwrites them."""
        if not 0 <= slot < self.num_slots:
            raise ValueError(f"slot {slot} out of range")
        if not self._occupied[slot]:
            raise ValueError(f"slot {slot} is already free")
        self._occupied[slot] = False
        for pg in self._slot_pages[slot]:
            self._ref[pg] -= 1
            if self._ref[pg] == 0:
                heapq.heappush(self._free_pages, pg)
        self._slot_pages[slot] = ()
        self.page_tables[slot] = 0
        self.lengths[slot] = 0
        heapq.heappush(self._free_slots, slot)

    # -- introspection -----------------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free_slots)

    @property
    def num_occupied(self) -> int:
        return self.num_slots - len(self._free_slots)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free_pages)

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of prompt tokens served from shared pages."""
        return (
            self.shared_tokens / self.prompt_tokens
            if self.prompt_tokens else 0.0
        )

    def occupied_slots(self) -> List[int]:
        return [i for i in range(self.num_slots) if self._occupied[i]]

    def kv_bytes(self) -> int:
        """Resident bytes of the page pool's KV-payload leaves (null
        page included — it is real allocated memory)."""
        total = 0
        for _, _, leaf in _frame_leaves(self.cache):
            total += int(leaf.size) * leaf.dtype.itemsize
        return total

    def device_page_table(self, slot: int) -> np.ndarray:
        return self.page_tables[slot].copy()

    def valid_mask(self) -> np.ndarray:
        """[S, max_len] bool over the DENSE view: True where a buffer
        position of an occupied slot holds a live token — the host
        statement of what each row's causal mask lets attention read."""
        mask = (
            np.arange(self.max_len)[None, :] < self.lengths[:, None]
        )
        mask[~self._occupied] = False
        return mask

    def check_consistency(self) -> None:
        """Audit the refcount/free-list/registry invariants; raises on
        the first violation. Tests call it after every lifecycle storm
        (mid-speculation eviction included)."""
        if sorted(self._free_slots) != [
            s for s in range(self.num_slots) if not self._occupied[s]
        ]:
            raise AssertionError("slot free list / occupancy flags drift")
        expect = np.zeros(self.num_pages + 1, np.int64)
        for slot, pages in enumerate(self._slot_pages):
            if pages and not self._occupied[slot]:
                raise AssertionError(f"free slot {slot} still holds pages")
            for pg in pages:
                if not 1 <= pg <= self.num_pages:
                    raise AssertionError(
                        f"slot {slot} references invalid page {pg}"
                    )
                expect[pg] += 1
        for key, pg in self._registry.items():
            if self._page_key.get(pg) != key:
                raise AssertionError(f"registry/page_key disagree on {pg}")
            expect[pg] += 1
        if len(self._page_key) != len(self._registry):
            raise AssertionError("page_key index out of sync with registry")
        if not np.array_equal(expect, self._ref.astype(np.int64)):
            bad = np.nonzero(expect != self._ref)[0]
            raise AssertionError(
                f"refcount drift on pages {bad.tolist()}: "
                f"expected {expect[bad].tolist()}, "
                f"recorded {self._ref[bad].tolist()}"
            )
        free = sorted(self._free_pages)
        if len(set(free)) != len(free):
            raise AssertionError("duplicate entries in the page free list")
        unref = sorted(
            pg for pg in range(1, self.num_pages + 1)
            if expect[pg] == 0
        )
        if free != unref:
            raise AssertionError(
                f"free list {free} != unreferenced pages {unref}"
            )
        if expect[0] != 0:
            raise AssertionError("null page 0 acquired a reference")
