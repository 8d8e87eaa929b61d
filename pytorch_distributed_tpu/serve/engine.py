"""Continuous-batching serve loop: admit/retire mid-flight, compile once.

The engine composes the pieces: a ``PagedKVPool`` (page-granular device
state + host page tables), a ``Scheduler`` (host dynamism), per-row
sampling, and a FIXED set of jitted programs, each compiled exactly once
for the engine's lifetime — the bounded-compile-count invariant, pinned
by tests:

* ``prefill``: one ``[1, prefill_chunk]`` model pass writing a chunk of
  one request's prompt into its pages and attending over them where
  they lie, as the tick does: a ``PagedView`` of the slot's one table
  row around the apply, the pool as its cache, the chunk's positions
  scattered into the donated leaves and the kernel's query-tiled body
  walking the row's pages up to the chunk's end. Samples the first
  token on the final chunk. With speculation enabled the SAME program
  also prefills the draft model's pages — still one program.
* ``decode``: one ``[S, 1]`` tick over ALL slots through the same
  ``generation.decode_step_body`` the offline ``generate`` scan uses —
  attending IN PLACE over the page pool (``ops/paged_attention``: the
  engine installs a ``PagedView`` around the traced model apply, new
  K/V lands via per-page scatters of only the deliberately-written
  positions, and attention streams the pages — no transient
  ``[S, max_len]`` dense view). Free / mid-prefill rows never write —
  the per-page write drops their rows.
* **length buckets** bound what the remaining dense span (the
  speculative draft's short context) and the paged streams' tables
  actually touch: widths round up to the live
  maximum's power-of-two page bucket instead of always ``max_len``,
  with the bucket width a STATIC jit argument — at most one program
  per occupied bucket (<= log2(max_pages) + 1 decode programs, each
  compiled exactly once, tracked per bucket in
  ``decode_buckets``/``prefill_buckets``).
* with ``SpecConfig``: the decode tick is replaced by ONE fused
  speculative program — k sequential draft proposals (a ``lax.scan`` of
  single-token draft steps) + one ``[S, k+1]`` target verify pass +
  per-row acceptance — emitting 1..k+1 tokens per request per tick for
  one host dispatch. Draft and verify could be two programs; fusing
  them halves dispatches and keeps the count at one, still counted via
  ``decode_compiles``.

Cache-rewind for rejected drafts is FREE here, unlike the offline
``speculative.generate_speculative`` (whose append-only cache pays
permanent slot bubbles): the pool's left-aligned position==buffer-slot
layout means a rejected draft's KV sits at positions >= the row's
accepted length — exactly where the next tick's chunk writes land
before anything attends them. No kv_mask, no compaction, no bubbles.

Static-shape invariant: no program's input shapes depend on which
requests are in flight. Parity invariant: every COMPLETED greedy or
sampled (non-speculative) request's token stream is bit-identical to a
solo ``generate(prompt, ..., rng=jax.random.PRNGKey(seed))``; under
speculation, greedy streams stay bit-identical (the verify accepts
exactly the target's own argmax prefix + correction) while sampled rows
follow Leviathan rejection sampling (distribution-exact, not
token-comparable — same contract as ``generate_speculative``).

Failure model (degrade, don't crash): ``serve.prefill``/``serve.decode``
fault sites fire per-request — a poisoned request is evicted as FAILED
mid-speculation or not, its slot and page references released (shared
pages survive for their other holders), and the engine keeps serving.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Deque, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_tpu.generation import (
    decode_step_body,
    model_max_len,
)
from pytorch_distributed_tpu.serve.disagg import (
    MigrationError,
    MigrationFrame,
    request_from_wire,
    request_to_wire,
)
from pytorch_distributed_tpu.ops.paged_attention import (
    PagedView,
    block_pages,
    is_chunk,
    paged_view,
    query_tiles,
    refuse_kernel_for,
    resolve_paged_attention_impl,
    tile_walk,
)
from pytorch_distributed_tpu.ops.moe import collect_route_stats
from pytorch_distributed_tpu.runtime import faults
from pytorch_distributed_tpu.runtime import tracing
from pytorch_distributed_tpu.serve.kv_slots import (
    PagedKVPool,
    extract_frames,
    frame_signature,
    gather_pages,
    kv_frame_width,
    scatter_kv,
    splice_frames,
    token_nbytes,
)
from pytorch_distributed_tpu.serve.sampling import (
    TOP_K_OFF,
    TOP_P_OFF,
    filter_logits_rows,
    sample_logits_rows,
)
from pytorch_distributed_tpu.serve.scheduler import (
    Request,
    RequestHandle,
    RequestStatus,
    Scheduler,
)
from pytorch_distributed_tpu.serve.telemetry import ServeTelemetry
from pytorch_distributed_tpu.speculative import speculative_accept
from pytorch_distributed_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def _with_route_stats(tokens, routed):
    """The tokens a program sends down, with its expert layers' routing
    counters (``ops.moe.collect_route_stats``: ``[layers, 3]``) behind
    them in the same int32 vector; a model without expert layers sends
    its tokens as they are."""
    if routed is None:
        return tokens
    return jnp.concatenate([tokens.reshape(-1), routed.reshape(-1)])


def _route_args(down, n_tokens: int):
    """Span args from what :func:`_with_route_stats` packed behind the
    ``n_tokens`` tokens, one entry an expert layer."""
    stats = np.asarray(down).reshape(-1)[n_tokens:].reshape(-1, 3)
    return {
        "expert_pairs": stats[:, 0].tolist(),
        "experts_hit": stats[:, 1].tolist(),
        "expert_peak": stats[:, 2].tolist(),
    }


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Opt-in speculative decoding for the engine tick.

    ``draft_model``/``draft_params`` must share the target's vocabulary
    and the ``generate`` decode contract; ``num_draft_tokens`` (k) is
    the static proposal width — every tick drafts k tokens and verifies
    them in one ``[S, k+1]`` target pass, emitting 1..k+1 tokens per
    decoding request.
    """

    draft_model: Any
    draft_params: Any
    num_draft_tokens: int = 4

    def __post_init__(self):
        if self.num_draft_tokens < 1:
            raise ValueError(
                f"num_draft_tokens must be >= 1, "
                f"got {self.num_draft_tokens}"
            )


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    num_slots: int = 4          # S: max concurrent in-flight requests
    max_len: int = 256          # per-request dense KV capacity
    prefill_chunk: int = 32     # static prompt-chunk width
    prefill_chunks_per_step: int = 1  # prefill/decode interleave ratio
    telemetry_every: int = 32   # engine steps between occupancy snapshots
    # paged pool knobs: page_size None -> largest power-of-2 divisor of
    # max_len (<= 32); num_pages None -> memory parity with the old
    # fixed [S, max_len] pool (size it DOWN to the realistic length mix
    # for the memory win); prefix_cache shares identical page-aligned
    # prompt prefixes copy-free via refcounts
    page_size: Optional[int] = None
    num_pages: Optional[int] = None
    prefix_cache: bool = True
    # r18 tiers: "solo" (the default — the bit-identical A/B baseline,
    # every pre-r18 code path byte-for-byte unchanged) serves requests
    # end to end; "prefill" fills pages and ships MigrationFrames via
    # ``outbox`` instead of decoding; "decode" owns the tick and takes
    # work via ``inject_migration`` only. All three roles drive the SAME
    # jitted programs — a role only changes which ones a request reaches.
    role: str = "solo"
    # fleet label: stamps telemetry records (engine_id gauge label) and
    # migration frames; None keeps the single-engine-implicit schema
    engine_id: Optional[str] = None

    def __post_init__(self):
        if self.role not in ("solo", "prefill", "decode"):
            raise ValueError(
                f"role must be 'solo', 'prefill' or 'decode', got "
                f"{self.role!r}"
            )
        if self.num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if self.prefill_chunks_per_step < 1:
            raise ValueError("prefill_chunks_per_step must be >= 1")
        if self.max_len < 2:
            raise ValueError("max_len must be >= 2 (1 prompt + 1 new)")
        if self.prefill_chunk > self.max_len:
            # every prompt rounds up to at least one chunk of KV slots,
            # so this config could never admit ANY request — fail at
            # construction naming the real culprit, not per-submit
            # blaming the prompt
            raise ValueError(
                f"prefill_chunk {self.prefill_chunk} > max_len "
                f"{self.max_len}: no request could ever be admitted"
            )
        if self.page_size is not None and (
            self.page_size < 1 or self.max_len % self.page_size
        ):
            raise ValueError(
                f"page_size {self.page_size} must divide max_len "
                f"{self.max_len} (the paged dense view is "
                f"max_pages * page_size wide)"
            )


class ServeEngine:
    """Single-threaded, deterministic serve loop.

    Drive it with ``submit()`` + ``step()`` (one scheduler iteration:
    deadline sweep -> cancellations -> admission -> prefill chunks ->
    decode tick), or ``run_until_drained()``. Tokens stream into each
    ``RequestHandle.tokens`` as they are emitted (or via
    ``handle.on_token``).

    ``params`` may be placed by any ``parallel/strategies.py`` strategy
    — the jitted programs follow the committed shardings (TP rules
    shard the per-slot compute exactly as they shard ``generate``).
    """

    def __init__(
        self,
        model,
        params,
        config: EngineConfig = EngineConfig(),
        *,
        spec: Optional[SpecConfig] = None,
        telemetry: Optional[ServeTelemetry] = None,
        prefix_store=None,
        clock=time.monotonic,
    ):
        self.model = model
        self.params = params
        self.config = config
        self.spec = spec
        self.role = config.role
        self.engine_id = config.engine_id
        if config.role != "solo" and spec is not None:
            # tiered speculation would also have to migrate the DRAFT
            # pool's pages and re-derive its rng chain — future work;
            # refuse loudly rather than ship a frame the decode tier
            # cannot faithfully adopt
            raise ValueError(
                f"role={config.role!r} requires spec=None: speculative "
                "decoding is solo-engine only (the draft cache does not "
                "ride the migration frame)"
            )
        if prefix_store is not None and spec is not None:
            raise ValueError(
                "prefix_store requires spec=None: store adoption splices "
                "target pages only, and a draft pool sharing the slot "
                "would miss the prefix"
            )
        self.telemetry = telemetry or ServeTelemetry(
            clock=clock, engine_id=config.engine_id
        )
        if self.telemetry.engine_id is None and config.engine_id:
            # caller-supplied telemetry inherits the fleet label so
            # merged multi-engine streams stay disambiguable
            self.telemetry.engine_id = config.engine_id
        self._clock = clock
        limit = model_max_len(model)
        if limit is not None and config.max_len > limit:
            raise ValueError(
                f"max_len {config.max_len} exceeds the model's maximum "
                f"sequence length {limit}"
            )
        self.pool = PagedKVPool(
            model, params, config.num_slots, config.max_len,
            page_size=config.page_size, num_pages=config.num_pages,
            prefix_cache=config.prefix_cache,
        )
        self.draft_pool = None
        self._spec_tail = 0
        if spec is not None:
            dlimit = model_max_len(spec.draft_model)
            if dlimit is not None and config.max_len > dlimit:
                raise ValueError(
                    f"max_len {config.max_len} exceeds the DRAFT "
                    f"model's maximum sequence length {dlimit}"
                )
            # the draft shares the target's page geometry so one chunk
            # stream and one joint prefix skip drive both caches
            self.draft_pool = PagedKVPool(
                spec.draft_model, spec.draft_params,
                config.num_slots, config.max_len,
                page_size=self.pool.page_size,
                num_pages=config.num_pages,
                prefix_cache=config.prefix_cache,
            )
            # verify writes up to k rejected-draft entries past the
            # emitted horizon — reserved at admission, checked at submit
            self._spec_tail = spec.num_draft_tokens
        self.scheduler = Scheduler(config.num_slots, config.prefill_chunk)
        # -- r18 fleet state ------------------------------------------------
        # one geometry string commits the pool's frame layout; every
        # migration packet and store access is fingerprint-checked
        # against it (the _verify_p2p DETAIL idiom, per hand-off)
        self.migration_signature = frame_signature(
            self.pool.cache, self.pool.page_size
        )
        #: prefill role: packed frames awaiting the router's pick-up
        self.outbox: Deque[MigrationFrame] = deque()
        #: decode/solo role: injected handles awaiting slot capacity
        self._inject_backlog: Deque[RequestHandle] = deque()
        self._store = prefix_store
        if prefix_store is not None:
            sig = getattr(prefix_store, "signature", None)
            if sig is None:
                # first engine to attach commits the fleet geometry
                prefix_store.signature = self.migration_signature
            elif sig != self.migration_signature:
                raise ValueError(
                    "prefix-store geometry mismatch at attach: store "
                    f"holds {sig!r}, this engine's pool is "
                    f"{self.migration_signature!r}"
                )
        self._holder = config.engine_id or f"engine-{id(self):x}"
        self.migrated_out = 0          # frames shipped (prefill role)
        self.migrated_in = 0           # frames spliced (decode/solo)
        self.store_published_pages = 0
        self.store_adopted_pages = 0
        S = config.num_slots
        mp = self.pool.max_pages
        # per-slot sampling/decode state lives ON DEVICE and is updated
        # in place: rows change only at request transitions (admission,
        # prefill-final, eviction), and the decode tick advances the
        # continuing rows inside the jitted program — so a steady-state
        # tick is ONE jit call plus one token fetch, no per-tick
        # host->device re-uploads (measured 2ms/tick of pure host
        # overhead before this). Stale rows of freed/mid-prefill slots
        # are harmless: their sampled tokens are discarded and their
        # pool writes are DROPPED (scatter keep-mask), so stale state
        # never reaches the persistent pages.
        self._toks = jnp.zeros(S, jnp.int32)
        self._lengths = jnp.zeros(S, jnp.int32)
        self._temps = jnp.zeros(S, jnp.float32)
        self._top_ks = jnp.full(S, TOP_K_OFF, jnp.int32)
        self._top_ps = jnp.full(S, TOP_P_OFF, jnp.float32)
        # old-style uint32 [2] keys: stackable/vmappable plain arrays
        # with the same threefry streams as jax.random.key
        self._keys = jnp.tile(jax.random.PRNGKey(0)[None, :], (S, 1))
        # device page tables (target + draft), updated only at admission
        self._pt = jnp.zeros((S, mp), jnp.int32)
        self._dpt = (
            jnp.zeros((S, mp), jnp.int32) if spec is not None else None
        )
        self._n_deadlines = 0  # live requests carrying a deadline
        self._any_cancel = False
        # the decoding set only changes at request transitions — cache
        # the (slot, handle) list and the device-side active mask so a
        # steady-state tick rebuilds neither
        self._decoding_dirty = True
        self._decoding_cached = []
        self._active_cached = None
        # decoding rows whose request samples (temperature > 0): what
        # the tick's sampler is about to observe on the device, kept
        # here so the tick spans can say it without a fetch
        self._sampling_rows = 0
        self._steps = 0
        self._decode_ticks = 0
        # armed only: (span, tokens + routing counters) of dispatched
        # chunks whose counters have not been read down yet
        self._route_pending: list = []
        self.prefill_compiles = 0
        self.decode_compiles = 0
        # length buckets: the static widths the prefill/decode programs
        # compile at — powers of two in pages, capped at max_pages
        self._buckets = self._bucket_list(mp)
        # per-bucket compile counts (the traced program bodies bump
        # them): the bounded-compile invariant is now "each occupied
        # bucket compiled EXACTLY once" — decode_compiles stays the
        # cumulative total across buckets
        self._decode_bucket_compiles: dict = {}
        self._prefill_bucket_compiles: dict = {}
        # the impl resolves ONCE: it is what the programs trace, and
        # what the tick spans' ``fetched_pages`` count
        self._resolved_impl = resolve_paged_attention_impl()
        if self._resolved_impl == "kernel":
            # fail at construction, not at the first decode compile
            refuse_kernel_for(quantized=getattr(
                getattr(model, "config", None), "kv_cache_quantize", None
            ) is not None, page_size=self.pool.page_size)
        # bytes of one token in one layer's widest leaf: the kernel's
        # block size follows it (the tick spans' ``fetched_pages``)
        self._token_bytes = token_nbytes(self.pool.cache)
        # speculative bookkeeping (raw per-verify acceptance; host ints)
        self.spec_verifies = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        # donation lets XLA update the page pools in place; XLA:CPU
        # cannot alias and would warn every call, so gate on backend
        donate = jax.default_backend() != "cpu"
        # distinct attributes per program (never rebound to a different
        # signature) so donation bookkeeping is auditable per call site
        self._prefill = self._decode = None
        self._prefill_spec = self._spec_tick = None
        # the bucket width rides as a STATIC argument: one compiled
        # program per occupied width, each counted by the traced body
        if spec is None:
            self._prefill = jax.jit(
                self._prefill_fn, donate_argnums=(1,) if donate else (),
                static_argnums=(14,),
            )
            # pool + the in-program-advanced rows (toks/lengths/keys)
            # are donated: each is replaced by its returned successor
            self._decode = jax.jit(
                self._decode_fn,
                donate_argnums=(1, 3, 4, 5) if donate else (),
                static_argnums=(10,),
            )
        else:
            self._prefill_spec = jax.jit(
                self._prefill_spec_fn,
                donate_argnums=(2, 3) if donate else (),
                static_argnums=(17,),
            )
            self._spec_tick = jax.jit(
                self._spec_fn,
                donate_argnums=(2, 3, 6, 7, 8) if donate else (),
                static_argnums=(13,),
            )
        # admission-time row setup as ONE jitted program: eager
        # .at[].set dispatches cost ~2.4ms EACH on this backend
        # (measured under cProfile — per-request transitions were half
        # the serving wall-clock), a fused compiled update is ~0.1ms
        self._admit_rows = jax.jit(self._admit_rows_fn)
        # migration admission writes a DECODING row directly (no
        # prefill pass): same fused-update rationale as _admit_rows
        self._inject_rows = jax.jit(self._inject_rows_fn)

    # -- jitted programs ---------------------------------------------------
    @staticmethod
    def _bucket_list(max_pages: int):
        """Power-of-two page widths up to (and always including) the
        full table — the static shapes the bucketed programs compile
        at. <= log2(max_pages) + 1 entries."""
        out, b = [], 1
        while b < max_pages:
            out.append(b)
            b *= 2
        out.append(max_pages)
        return out

    def _bucket_for(self, pages: int) -> int:
        for b in self._buckets:
            if b >= pages:
                return b
        return self._buckets[-1]

    def _prefill_chunk_body(self, model, params, pool, cache, pt, ids,
                            slot, start, n_pages):
        """One model's chunk prefill over its page pool (``pool`` owns
        ``cache``), attending where the pool lies, as the tick does: a
        ``PagedView`` of the slot's one table row — only the leading
        ``n_pages`` bucket the chunk can reach — around the ``[1, C]``
        apply, the POOL as its cache. ``decode_cache`` scatters the
        chunk's ``C`` positions into the donated leaves (padded
        final-chunk positions included — they stay inside the slot's
        reserved private span and are overwritten or masked, as
        before), attention walks the row's pages up to ``start + C``
        (``ops.paged_attention``: the query-tiled body of the kernel;
        under ``"gather"`` the bucket's slab and the dense math; a
        latent leaf's chunk gathers the bucket's frames and decodes
        them, ``models/deepseek_v3.py``), and the leaves ride the layer
        loop as its carry; nothing else of the pool moves. Returns
        (chunk logits, updated pool)."""
        C = self.config.prefill_chunk
        row_pt = jax.lax.dynamic_slice_in_dim(pt, slot, 1, axis=0)
        row_pt = jax.lax.slice_in_dim(row_pt, 0, n_pages, axis=1)
        with paged_view(PagedView(
            page_tables=row_pt, keep=jnp.ones((1,), bool),
            page_size=pool.page_size,
        )):
            logits, state = model.apply(
                {"params": params, "cache": cache},
                ids,
                decode=True,
                cache_len=self.config.max_len,
                mutable=["cache", "intermediates"],
                positions=(start + jnp.arange(C))[None, :],
                write_pos=jnp.asarray(start, jnp.int32)[None],
            )
        return logits, state["cache"], collect_route_stats(
            state.get("intermediates", {})
        )

    def _prefill_tail(self, logits, slot, start, last_idx, final, toks,
                      lengths, keys, temps, top_ks, top_ps):
        """Shared epilogue: advance the device length cursor and, on the
        final chunk, sample/persist the first token + rng split."""
        # the device length cursor advances with EVERY chunk, not just
        # the final one — a decode tick between chunks must see the
        # cursor at the NEXT chunk's start (its write is dropped, but
        # its positions/mask derive from the cursor)
        lengths = lengths.at[slot].set(start + last_idx + 1)
        # rng discipline mirrors generate(): ONE split before the first
        # token, persisted (with the token) only on the final chunk
        pair = jax.random.split(keys[slot])
        last = jax.lax.dynamic_index_in_dim(
            logits, last_idx, axis=1, keepdims=False
        )  # [1, V] — the chunk's last REAL prompt column
        # only the final chunk's token is kept: no other buys a draw
        tok = sample_logits_rows(
            last, pair[1][None], temps[slot][None],
            top_ks[slot][None], top_ps[slot][None],
            live=jnp.reshape(final, (1,)),
        )[0]
        keys = jnp.where(final, keys.at[slot].set(pair[0]), keys)
        toks = jnp.where(final, toks.at[slot].set(tok), toks)
        return tok, toks, lengths, keys

    def _prefill_fn(self, params, cache, pt, ids, slot, start, last_idx,
                    final, toks, lengths, keys, temps, top_ks, top_ps,
                    n_pages):
        # traced once per (engine lifetime, bucket width) — python side
        # effects count compiles, cumulatively and per bucket (the
        # bounded-compile invariant, pinned by tests)
        self.prefill_compiles += 1
        self._prefill_bucket_compiles[n_pages] = (
            self._prefill_bucket_compiles.get(n_pages, 0) + 1
        )
        logits, cache, routed = self._prefill_chunk_body(
            self.model, params, self.pool, cache, pt, ids, slot, start,
            n_pages,
        )
        tok, toks, lengths, keys = self._prefill_tail(
            logits, slot, start, last_idx, final, toks, lengths, keys,
            temps, top_ks, top_ps,
        )
        return cache, _with_route_stats(tok, routed), toks, lengths, keys

    def _prefill_spec_fn(self, params, dparams, cache, dcache, pt, dpt,
                         ids, slot, start, last_idx, final, toks,
                         lengths, keys, temps, top_ks, top_ps, n_pages):
        """Speculative prefill: the SAME chunk through target AND draft
        (the draft needs the prompt's KV before it can propose) — one
        program per bucket, one dispatch per chunk."""
        self.prefill_compiles += 1
        self._prefill_bucket_compiles[n_pages] = (
            self._prefill_bucket_compiles.get(n_pages, 0) + 1
        )
        logits, cache, _ = self._prefill_chunk_body(
            self.model, params, self.pool, cache, pt, ids, slot, start,
            n_pages,
        )
        _, dcache, _ = self._prefill_chunk_body(
            self.spec.draft_model, dparams, self.draft_pool, dcache, dpt,
            ids, slot, start, n_pages,
        )
        tok, toks, lengths, keys = self._prefill_tail(
            logits, slot, start, last_idx, final, toks, lengths, keys,
            temps, top_ks, top_ps,
        )
        return cache, dcache, tok, toks, lengths, keys

    def _admit_rows_fn(self, temps, top_ks, top_ps, keys, lengths, pt,
                       dpt, slot, temp, top_k, top_p, seed, skip,
                       pt_row, dpt_row):
        # the write cursor parks at `skip` — the first position the
        # request's own prefill will write. Everything before it is
        # shared-prefix pages (read-only by the CoW discipline); the
        # decode tick's write for this inactive row is dropped anyway,
        # but positions/masks derive from the cursor and must never
        # point inside a shared page.
        out = (
            temps.at[slot].set(temp),
            top_ks.at[slot].set(top_k),
            top_ps.at[slot].set(top_p),
            keys.at[slot].set(jax.random.PRNGKey(seed)),
            lengths.at[slot].set(skip),
            pt.at[slot].set(pt_row),
        )
        if dpt is not None:
            out = out + (dpt.at[slot].set(dpt_row),)
        return out

    def _inject_rows_fn(self, temps, top_ks, top_ps, keys, lengths, toks,
                        pt, slot, temp, top_k, top_p, seed, length, tok,
                        pt_row):
        # re-derive the row state the prefill tier's final chunk left
        # behind instead of shipping it: generate()'s discipline is ONE
        # split of PRNGKey(seed) before the first token, so the decode
        # key is split(...)[0], the pending token is the shipped first
        # token, and the cursor sits at prompt_len — bit-identical to
        # the solo engine's post-prefill row by construction
        key0 = jax.random.split(jax.random.PRNGKey(seed))[0]
        return (
            temps.at[slot].set(temp),
            top_ks.at[slot].set(top_k),
            top_ps.at[slot].set(top_p),
            keys.at[slot].set(key0),
            lengths.at[slot].set(length),
            toks.at[slot].set(tok),
            pt.at[slot].set(pt_row),
        )

    def _decode_fn(self, params, cache, pt, toks, lengths, keys, temps,
                   top_ks, top_ps, active, n_pages):
        self.decode_compiles += 1
        self._decode_bucket_compiles[n_pages] = (
            self._decode_bucket_compiles.get(n_pages, 0) + 1
        )
        # attend in place over the pool: decode_cache writes the new
        # token through per-page scatters (inactive rows drop theirs)
        # and attention streams the bucket-sliced tables — no dense
        # intermediate, no scatter-back. The pool leaves ride the layer
        # loop as its carry (models/scan.py) and come back as the same
        # buffers, donated in and aliased out (scripts/pool_hlo_check.py
        # reads that off the compiled program, where it has to hold: the
        # jaxpr alone can say "the returned cache IS the pool" over a
        # program that copies every leaf)
        ptb = jax.lax.slice_in_dim(pt, 0, n_pages, axis=1)
        with paged_view(PagedView(
            page_tables=ptb, keep=active,
            page_size=self.pool.page_size,
        )):
            last, cache, sown = decode_step_body(
                self.model, params, cache, toks,
                cache_len=self.config.max_len,
                positions=lengths[:, None],
                write_pos=lengths, with_intermediates=True,
            )
        pair = jax.vmap(jax.random.split)(keys)  # [S, 2, 2]
        # the sampler's cost follows the LIVE rows' parameters: a freed
        # slot's stale temperature must not buy the batch a sort
        nxt = sample_logits_rows(
            last, pair[:, 1], temps, top_ks, top_ps, live=active
        )
        # advance ONLY the decoding rows in place: the continuing token
        # becomes next tick's input, the rng chain splits once, the
        # length grows one — inactive rows (free / mid-prefill) keep
        # their state so their request transitions stay host-authored
        toks_out = jnp.where(active, nxt, toks)
        lengths_out = lengths + active.astype(jnp.int32)
        keys_out = jnp.where(active[:, None], pair[:, 0], keys)
        # an expert layer's routing counters ride down with the tokens
        # the host fetches anyway: no transfer or sync of their own
        down = _with_route_stats(nxt, collect_route_stats(sown))
        return cache, down, toks_out, lengths_out, keys_out

    def _spec_fn(self, params, dparams, cache, dcache, pt, dpt, toks,
                 lengths, keys, temps, top_ks, top_ps, active, n_pages):
        """The fused speculative tick: k draft proposals -> one [S, k+1]
        target verify -> per-row acceptance -> page scatters.

        Greedy rows accept the longest prefix where the target's own
        argmax agrees (output EXACTLY the target's greedy stream);
        sampled rows run Leviathan rejection sampling per row with that
        row's filtered distributions. Emits ``a+1`` tokens per active
        row; the host truncates at eos / max_new (any truncation
        retires the request, so device/host state never diverges for a
        row that keeps decoding).

        The DRAFT keeps a dense view — its k sequential single-token
        steps re-read the whole live context every step, the one shape
        a dense span still wins — bucket-sliced to ``n_pages`` instead
        of ``max_len``-wide; the target verify
        attends in place over the pool like the plain tick, with the
        ``[S, k+1]`` query block riding the same paged primitive.
        """
        self.decode_compiles += 1
        self._decode_bucket_compiles[n_pages] = (
            self._decode_bucket_compiles.get(n_pages, 0) + 1
        )
        k = self.spec.num_draft_tokens
        S = self.config.num_slots
        max_len = self.config.max_len
        width = n_pages * self.pool.page_size
        dpt = jax.lax.slice_in_dim(dpt, 0, n_pages, axis=1)
        idx = jnp.arange(k + 1)[None, :]
        pair = jax.vmap(jax.random.split)(keys)   # [S, 2, 2]
        ticket = pair[:, 1]  # per-row key budget for this tick's draws
        greedy_row = temps <= 0
        # the sampled machinery (per-row filtered distributions — a
        # vocab sort per position — plus rejection sampling) is real
        # compute the all-greedy steady state shouldn't pay: one
        # runtime branch skips it when no live row samples (a freed
        # slot keeps its last request's temperature: masked by `active`)
        any_sampled = jnp.any(active & ~greedy_row)

        dense_d = gather_pages(dcache, dpt, self.draft_pool.tails)

        def dstep(carry, j):
            dense_d, tok = carry
            logits, dense_d = decode_step_body(
                self.spec.draft_model, dparams, dense_d, tok,
                cache_len=width,
                positions=(lengths + j)[:, None],
                write_pos=lengths + j,
            )
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

            def propose_sampled(lg):
                filt = filter_logits_rows(lg, temps, top_ks, top_ps)
                sub = jax.vmap(jax.random.fold_in, in_axes=(0, None))(
                    ticket, 1 + j
                )
                sampled = jax.vmap(
                    lambda kk, row: jax.random.categorical(
                        kk, row, axis=-1
                    )
                )(sub, filt).astype(jnp.int32)
                return (
                    jnp.where(greedy_row, greedy, sampled),
                    jax.nn.softmax(filt, axis=-1),
                )

            nxt, q = jax.lax.cond(
                any_sampled, propose_sampled,
                lambda lg: (greedy, jnp.zeros(lg.shape, jnp.float32)),
                logits,
            )
            return (dense_d, nxt), (nxt, q)

        (dense_d, last_prop), (drafts, qs) = jax.lax.scan(
            dstep, (dense_d, toks), jnp.arange(k), length=k
        )
        # one sampling-free feed caches the FINAL proposal's K/V
        # (speculative.py's dfill, carried over): a fully accepted
        # round advances past position lengths+k, and without this
        # write that position would hold a permanent hole the draft
        # attends forever after — acceptance quietly degrades while
        # emitted tokens stay correct. For partial acceptance the
        # entry is rejected-tail garbage the next round overwrites
        # before any query reaches it, like every other rejected slot.
        _, dense_d = decode_step_body(
            self.spec.draft_model, dparams, dense_d, last_prop,
            cache_len=width,
            positions=(lengths + k)[:, None],
            write_pos=lengths + k,
        )
        drafts = drafts.T                      # [S, k]
        q_probs = jnp.moveaxis(qs, 0, 1)       # [S, k, V]
        dpos = lengths[:, None] + jnp.arange(k + 1)[None, :]
        dcache = scatter_kv(
            dcache, dense_d, dpt, dpos,
            active[:, None] & jnp.ones((1, k + 1), bool),
        )

        # ---- verify: one chunked target pass scores the proposal ----
        chunk = jnp.concatenate([toks[:, None], drafts], axis=1)
        # the [S, k+1] verify attends in place over the pool: the k+1
        # K/V entries land via per-page scatters (inactive rows dropped)
        # and the paged primitive streams the bucket
        ptb = jax.lax.slice_in_dim(pt, 0, n_pages, axis=1)
        with paged_view(PagedView(
            page_tables=ptb, keep=active,
            page_size=self.pool.page_size,
        )):
            logits, st = self.model.apply(
                {"params": params, "cache": cache},
                chunk, decode=True, cache_len=max_len,
                mutable=["cache"],
                positions=lengths[:, None] + idx,
                write_pos=lengths,
            )
        cache = st["cache"]

        # ---- acceptance ----
        # greedy: the longest draft prefix matching the target's own
        # argmax chain, correction = the target's next choice — the
        # emitted stream IS target-greedy, token for token
        preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S, k+1]
        match = drafts == preds[:, :k]
        a_g = jnp.sum(
            jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1
        )
        corr_g = jnp.take_along_axis(preds, a_g[:, None], axis=1)[:, 0]

        def accept_sampled(lg):
            # Leviathan rejection sampling per row with the row's own
            # filtered target/draft distributions and its own key chain
            p_filt = jax.vmap(
                lambda col: filter_logits_rows(
                    col, temps, top_ks, top_ps
                ),
                in_axes=1, out_axes=1,
            )(lg)
            p_probs = jax.nn.softmax(p_filt, axis=-1)  # [S, k+1, V]
            acc_keys = jax.vmap(
                jax.random.fold_in, in_axes=(0, None)
            )(ticket, 0)
            a_s, corr_s = jax.vmap(
                lambda p, q, d, kk: speculative_accept(
                    p[None], q[None], d[None], kk
                )
            )(p_probs, q_probs, drafts, acc_keys)
            return (
                jnp.where(greedy_row, a_g, a_s[:, 0]),
                jnp.where(greedy_row, corr_g, corr_s[:, 0]),
            )

        a, corr = jax.lax.cond(
            any_sampled, accept_sampled, lambda lg: (a_g, corr_g),
            logits,
        )

        drafts_ext = jnp.concatenate(
            [drafts, jnp.zeros((S, 1), jnp.int32)], axis=1
        )
        emit = jnp.where(idx < a[:, None], drafts_ext, corr[:, None])
        # the correction is the round's last emitted token — next
        # tick's input, its KV not yet written (it was an OUTPUT), so
        # next tick's chunk write at the new length caches it and
        # overwrites the first rejected entry in the same stroke
        toks_out = jnp.where(active, corr, toks)
        lengths_out = lengths + jnp.where(
            active, a + 1, jnp.zeros_like(a)
        )
        keys_out = jnp.where(active[:, None], pair[:, 0], keys)
        # accepted count rides as one extra column so the host pays a
        # SINGLE device fetch per tick (two syncs measurably hurt the
        # dispatch-bound regime speculation targets)
        emit_acc = jnp.concatenate([emit, a[:, None]], axis=1)
        return (
            cache, dcache, emit_acc, toks_out, lengths_out, keys_out,
        )

    # -- intake ------------------------------------------------------------
    def _validate_request(self, request: Request) -> None:
        cfg = self.config
        P = request.prompt_len
        chunks = -(-P // cfg.prefill_chunk)  # ceil
        if chunks * cfg.prefill_chunk > cfg.max_len:
            # the final chunk's [C]-wide write would clamp at the buffer
            # edge and corrupt earlier positions — refuse up front
            raise ValueError(
                f"prompt ({P} tokens) rounds up to "
                f"{chunks * cfg.prefill_chunk} chunked-prefill slots, "
                f"exceeding max_len {cfg.max_len}"
            )
        if P + request.max_new_tokens + self._spec_tail > cfg.max_len:
            tail_note = (
                f" + {self._spec_tail} speculative-verify slots"
                if self._spec_tail else ""
            )
            raise ValueError(
                f"prompt ({P}) + max_new_tokens "
                f"({request.max_new_tokens}){tail_note} exceeds the "
                f"engine's max_len {cfg.max_len}"
            )

    def submit(self, request: Request) -> RequestHandle:
        """Validate + enqueue; returns the streaming handle."""
        if self.role == "decode":
            raise RuntimeError(
                "decode-tier engines take work via inject_migration() "
                "only — route submissions to a prefill or solo engine"
            )
        span = (
            tracing._NULL_SPAN if tracing._tracer is None
            else tracing.span("serve.submit", request=request.request_id)
        )
        with span:
            self._validate_request(request)
            handle = RequestHandle(request, submitted_at=self._clock())
            if request.deadline_s is not None:
                self._n_deadlines += 1
            self.scheduler.enqueue(handle)
            self.telemetry.record_submit(handle)
        return handle

    def cancel(self, request_id: str) -> bool:
        """Flag a live request for eviction at the next step."""
        h = self.scheduler.find(request_id)
        if h is None:
            return False
        h._cancel = True
        self._any_cancel = True
        return True

    # -- migration intake (decode/solo roles) ------------------------------
    def inject_migration(
        self, frame: MigrationFrame, submitted_at: Optional[float] = None,
    ) -> RequestHandle:
        """Adopt a prefill-tier frame: fingerprint-check it, rebuild the
        ``Request``, and queue it for direct-to-DECODING admission at
        the next ``step()``. ``submitted_at`` (the router's original
        submit time) keeps TTFT honest across the tier hand-off."""
        if self.role == "prefill":
            raise RuntimeError(
                "prefill-tier engines ship frames via outbox; they do "
                "not accept them"
            )
        if frame.signature != self.migration_signature:
            raise MigrationError(
                "migration frame geometry mismatch: this pool is "
                f"{self.migration_signature!r}, frame declares "
                f"{frame.signature!r} — refusing the splice"
            )
        req = request_from_wire(frame.request)
        self._validate_request(req)
        if frame.prompt_len != req.prompt_len:
            raise MigrationError(
                f"frame prompt_len {frame.prompt_len} disagrees with "
                f"its own request ({req.prompt_len} tokens)"
            )
        want_pages = -(-frame.prompt_len // self.pool.page_size)
        if frame.n_pages != want_pages:
            raise MigrationError(
                f"frame ships {frame.n_pages} pages but a "
                f"{frame.prompt_len}-token prompt spans {want_pages} "
                f"at page_size {self.pool.page_size}"
            )
        h = RequestHandle(
            req,
            submitted_at=(
                self._clock() if submitted_at is None else submitted_at
            ),
        )
        if req.deadline_s is not None:
            self._n_deadlines += 1
        h._mig_frame = frame
        self._inject_backlog.append(h)
        self.telemetry.record_submit(h)
        return h

    def _admit_injected(self, h: RequestHandle) -> bool:
        """Bind an injected handle to a slot: allocate the same span the
        solo path would (chunk-rounded prompt + max_new + tail), splice
        the frame's page bytes in, and write the decode row the prefill
        tier's final chunk would have left — the handle enters DECODING
        with no prefill pass. Returns False when no slot/pages fit yet
        (strict FIFO over the backlog, like the queue)."""
        frame: MigrationFrame = h._mig_frame
        req = h.request
        # keys=[] disables BOTH the shared-prefix walk and registration:
        # the arriving pages are private splices, and registering them
        # would advertise pages this engine never hashed. Delta
        # migration (shipping only the pages the decode side lacks) is
        # the documented future step.
        lease = self.pool.allocate(
            req.prompt_ids, max_new=req.max_new_tokens,
            chunk=self.config.prefill_chunk, tail=self._spec_tail,
            keys=[],
        )
        if lease is None:
            return False
        self.scheduler.adopt(h, lease)
        pages = np.asarray(lease.page_row[:frame.n_pages], np.int32)
        span = (
            tracing._NULL_SPAN if tracing._tracer is None
            else tracing.span(
                "serve.migrate_in", request=req.request_id,
                pages=int(frame.n_pages), nbytes=frame.payload_nbytes,
            )
        )
        with span:
            self.pool.cache = splice_frames(
                self.pool.cache, pages, frame.payload
            )
        self.pool.lengths[lease.slot] = frame.prompt_len
        (
            self._temps, self._top_ks, self._top_ps, self._keys,
            self._lengths, self._toks, self._pt,
        ) = self._inject_rows(
            self._temps, self._top_ks, self._top_ps, self._keys,
            self._lengths, self._toks, self._pt, lease.slot,
            req.temperature,
            TOP_K_OFF if req.top_k is None else req.top_k,
            TOP_P_OFF if req.top_p is None else req.top_p,
            req.seed, frame.prompt_len, frame.first_token,
            lease.page_row,
        )
        self._decoding_dirty = True
        self.migrated_in += 1
        h._mig_frame = None
        self._emit(h, int(frame.first_token))
        return True

    def _drain_inject_backlog(self) -> None:
        now = self._clock()
        while self._inject_backlog:
            h = self._inject_backlog[0]
            if h.done:  # cancelled/expired while waiting
                self._inject_backlog.popleft()
                continue
            if h.deadline_at is not None and now >= h.deadline_at:
                self._inject_backlog.popleft()
                self._finish(h, RequestStatus.EXPIRED)
                continue
            if not self._admit_injected(h):
                break
            self._inject_backlog.popleft()

    # -- the loop ----------------------------------------------------------
    def has_work(self) -> bool:
        # O(1): the drive loop asks once per step — no live-handle list
        return bool(
            self.scheduler.queue or self.scheduler.by_slot
            or self._inject_backlog
        )

    def step(self) -> bool:
        """One scheduler iteration; returns True when any device work
        ran (a prefill chunk or a decode tick).

        Armed, a step is one span tree: ``serve.step`` at the root and
        every span recorded during it below it (``parent_id``), so the
        host's own time in a step is the root's duration minus its
        ``serve.token_fetch`` / ``serve.first_token_fetch`` descendants
        (the device waits)."""
        with tracing.span("serve.step") as sp:
            self._steps += 1
            # the sweeps scan every live handle — skip them entirely on
            # the (typical) ticks where no deadline or cancellation exists
            if self._n_deadlines or self._any_cancel or self._inject_backlog:
                with tracing.span("serve.sweep"):
                    self._sweep()
            if self.scheduler.queue:
                # chain keys, allocate, the blocked head-of-line retry
                with tracing.span("serve.schedule"):
                    if self._store is not None:
                        self._adopt_from_store()
                    admitted = self.scheduler.admit(
                        self.pool, self.draft_pool, tail=self._spec_tail
                    )
                for h in admitted:
                    span = (
                        tracing._NULL_SPAN if tracing._tracer is None
                        else tracing.span(
                            "serve.admit", request=h.request.request_id
                        )
                    )
                    with span:
                        self._configure_slot(h)
            chunks = self._run_prefill()
            decoded = self._run_decode()
            if self.config.telemetry_every and (
                self._steps % self.config.telemetry_every == 0
            ):
                self._snapshot()
            did = bool(chunks or decoded)
            if tracing._tracer is not None:
                sp.set(did=did, prefill_chunks=chunks, decoded=decoded)
        return did

    def _sweep(self) -> None:
        """Deadlines, cancellations and the migration backlog."""
        if self._n_deadlines:
            now = self._clock()
            for h in self.scheduler.sweep_expired(now):
                self._finish(h, RequestStatus.EXPIRED)
        if self._any_cancel:
            self._any_cancel = False
            for h in self.scheduler.sweep_cancelled():
                self._finish(h, RequestStatus.CANCELLED)
        if self._inject_backlog:
            self._drain_inject_backlog()

    # -- length buckets ----------------------------------------------------
    def _compile_note(self, kind: str, n_pages: int) -> str:
        """Recompile-sentinel key: per bucket when buckets exist (each
        bucket is its own program with its own once-contract); the
        plain name when exactly one width exists (a one-page table)."""
        if len(self._buckets) == 1:
            return f"serve.{kind}"
        return f"serve.{kind}[b{n_pages}]"

    def _tick_bucket(self, decoding) -> int:
        """The static page width this tick's programs run at: the
        smallest bucket covering every ACTIVE row's reads and writes
        (max live length + the tick's write span). Inactive rows may
        point beyond it — their reads are discarded and their writes
        dropped, so the clamp is harmless by construction."""
        if resolve_paged_attention_impl() != self._resolved_impl:
            # set_paged_attention_impl() cleared the jit caches: the
            # next dispatch would retrace (breaking the compiled-once-
            # per-bucket contract) under a backend the engine was not
            # checked against at construction — refuse loudly
            raise RuntimeError(
                f"paged-attention impl changed under a live engine "
                f"(engine resolved {self._resolved_impl!r}, flag now "
                f"resolves {resolve_paged_attention_impl()!r}) — "
                f"construct a new ServeEngine after "
                f"set_paged_attention_impl()"
            )
        W = 1 if self.spec is None else self.spec.num_draft_tokens + 1
        need = max(
            int(self.pool.lengths[slot]) for slot, _ in decoding
        ) + W
        return self._bucket_for(-(-need // self.pool.page_size))

    @property
    def decode_buckets(self):
        """Bucket widths (pages) the decode tick has compiled at."""
        return set(self._decode_bucket_compiles)

    @property
    def prefill_buckets(self):
        return set(self._prefill_bucket_compiles)

    def precompile_decode_buckets(self) -> None:
        """Compile every decode-tick bucket with a no-op dispatch so
        serving never pays a compile mid-measurement.

        All rows ride as INACTIVE: pool writes are dropped by the keep
        gate, and toks/lengths/keys pass through their ``where(active,
        ...)`` untouched — device state is semantically unchanged.
        ``serve.loadgen.warm_up`` calls this after its warm request; a
        test driving the engine directly still sees one compile per
        OCCUPIED bucket.
        """
        idle = jnp.zeros(self.config.num_slots, bool)
        for n in self._buckets:
            if self.spec is None:
                (
                    self.pool.cache, _, self._toks, self._lengths,
                    self._keys,
                ) = self._decode(
                    self.params, self.pool.cache, self._pt, self._toks,
                    self._lengths, self._keys, self._temps,
                    self._top_ks, self._top_ps, idle, n,
                )
            else:
                (
                    self.pool.cache, self.draft_pool.cache, _,
                    self._toks, self._lengths, self._keys,
                ) = self._spec_tick(
                    self.params, self.spec.draft_params,
                    self.pool.cache, self.draft_pool.cache,
                    self._pt, self._dpt, self._toks, self._lengths,
                    self._keys, self._temps, self._top_ks,
                    self._top_ps, idle, n,
                )

    def trace_decode(self, n_pages: int):
        """The plain decode tick at bucket ``n_pages``, traced but
        neither compiled nor run (``jax.stages.Traced``): ``.lower()``
        gives the program text — how chip_smoke.py and
        tests/test_tpu_lowering.py establish that the tick really
        carries the Mosaic paged-attention kernel. The compile ledger
        is left as found: this trace serves nothing."""
        if self._decode is None:
            raise ValueError("trace_decode covers the plain (spec=None) tick")
        ledger = (self.decode_compiles, dict(self._decode_bucket_compiles))
        try:
            return self._decode.trace(
                self.params, self.pool.cache, self._pt, self._toks,
                self._lengths, self._keys, self._temps, self._top_ks,
                self._top_ps, jnp.zeros(self.config.num_slots, bool),
                n_pages,
            )
        finally:
            self.decode_compiles, self._decode_bucket_compiles = ledger

    def _snapshot(self) -> None:
        pool = self.pool
        gauges = dict(
            pages_in_use=pool.pages_in_use,
            pages_total=pool.num_pages,
            page_occupancy=(
                pool.pages_in_use / pool.num_pages if pool.num_pages
                else 0.0
            ),
            prefix_hit_rate=pool.prefix_hit_rate,
        )
        if self.spec is not None:
            gauges.update(
                spec_verifies=self.spec_verifies,
                spec_drafted=self.spec_drafted,
                spec_accepted=self.spec_accepted,
            )
        self.telemetry.record_snapshot(
            queue_depth=self.scheduler.queue_depth(),
            slots_occupied=pool.num_occupied,
            slots_total=pool.num_slots,
            decode_ticks=self._decode_ticks,
            **gauges,
        )

    def run_until_drained(self, max_steps: int = 1_000_000) -> None:
        """Step until every submitted request reaches a terminal state."""
        for _ in range(max_steps):
            if not self.has_work():
                return
            self.step()
        raise RuntimeError(
            f"engine did not drain within {max_steps} steps "
            f"({len(self.scheduler.live_handles())} requests live)"
        )

    # -- cross-engine prefix store (r18) -----------------------------------
    def _adopt_from_store(self) -> None:
        """Walk each queued request's chain keys once: pages the FLEET
        already prefilled (store hit) but this pool doesn't hold are
        claimed (``adopt_page``) and spliced in, so the normal
        ``allocate`` path then shares them copy-free — the hot system
        prompt is prefilled once per fleet, not once per engine. Stops
        at the first miss (chain contiguity); any failure to claim a
        page is a skipped optimization, never an error."""
        pool = self.pool
        if not pool.prefix_cache:
            return
        # a handle is re-walked only when the store has grown since its
        # last walk (``puts`` moved): a queued request that missed
        # yesterday adopts the page a PEER published today, and the
        # steady state pays zero store traffic per step
        version = getattr(self._store, "puts", None)
        for h in self.scheduler.queue:
            if getattr(h, "_store_walked", None) == version:
                continue
            h._store_walked = version
            req = h.request
            if h._chain_keys is None:
                h._chain_keys = pool.chain_keys(req.prompt_ids)
            cap = (req.prompt_len - 1) // pool.page_size
            for key in h._chain_keys[:cap]:
                if key in pool._registry:
                    continue  # already local (own prefill or adoption)
                payload = self._store.get(
                    key, holder=self._holder,
                    signature=self.migration_signature,
                )
                if payload is None:
                    break
                pg = pool.adopt_page(key)
                if pg is None:
                    break
                pool.cache = splice_frames(
                    pool.cache, np.asarray([pg], np.int32), payload
                )
                self.store_adopted_pages += 1

    def _publish_prefixes(self, h: RequestHandle) -> None:
        """Push the finished prompt's full pages the store lacks (first
        writer wins — a racing peer's duplicate is dropped unread)."""
        lease = h._lease
        row = self.pool.page_tables[lease.slot]
        for i, key in enumerate(lease.page_keys):
            if key in self._store:
                continue
            payload = extract_frames(
                self.pool.cache, np.asarray([row[i]], np.int32)
            )
            if self._store.put(
                key, payload, holder=self._holder,
                signature=self.migration_signature,
            ):
                self.store_published_pages += 1

    # -- migration packing (prefill role) ----------------------------------
    def _pack_migration(self, h: RequestHandle, first_token: int):
        """Freeze a finished prefill into a MigrationFrame — called
        strictly BEFORE ``_finish`` releases the slot (packing reads
        the live pages). Ships ``ceil(P / page_size)`` pages: every
        position < P lives there; bytes beyond P in the last page are
        garbage on BOTH tiers and never attended before overwrite."""
        req = h.request
        lease = h._lease
        n = -(-req.prompt_len // self.pool.page_size)
        pages = np.asarray(
            self.pool.page_tables[lease.slot][:n], np.int32
        )
        payload = extract_frames(self.pool.cache, pages)
        return MigrationFrame(
            request=request_to_wire(req),
            first_token=int(first_token),
            prompt_len=req.prompt_len,
            n_pages=n,
            signature=self.migration_signature,
            payload=payload,
            src_engine=self.engine_id or "",
        )

    # -- phase bodies ------------------------------------------------------
    def _run_prefill(self) -> int:
        """Plan and dispatch this step's prefill chunks; returns how
        many were dispatched."""
        cfg = self.config
        with tracing.span("serve.prefill_plan"):
            plans = self.scheduler.plan_prefill(cfg.prefill_chunks_per_step)
            padded = []
            for plan in plans:
                ids = np.zeros((1, cfg.prefill_chunk), np.int32)
                ids[0, :plan.chunk_len] = plan.ids
                padded.append(ids)
        chunks = 0
        for plan, ids in zip(plans, padded):
            h = plan.handle
            if h.done:  # evicted earlier in this very step's plan list
                continue
            if faults.active():
                try:
                    faults.check("serve.prefill", path=h.request.request_id)
                except faults.InjectedFault as e:
                    self._finish(h, RequestStatus.FAILED, error=e)
                    continue
            slot = h.slot
            # the chunk can reach positions [0, start + C): gather the
            # smallest bucket covering them, not the max_len-wide row
            n_pages = self._bucket_for(
                -(-(plan.start + cfg.prefill_chunk)
                  // self.pool.page_size)
            )
            # scalars pass as plain python values (weak-typed, no
            # retrace); ALL slot-row updates — per-chunk length cursor,
            # final-chunk key/token persist — happen inside the one
            # compiled program (eager .at[].set is ms-scale here)
            span = (
                tracing._NULL_SPAN if tracing._tracer is None
                else tracing.span(
                    "serve.prefill_chunk", request=h.request.request_id,
                    n_pages=n_pages, start=plan.start, final=plan.final,
                    # the one row's walk: pages up to start + C
                    **self._walk_pages(
                        [plan.start], cfg.prefill_chunk, n_pages, n_pages
                    ),
                )
            )
            with span:
                if self.spec is None:
                    tok = self._dispatch_prefill(ids, slot, plan, n_pages)
                else:
                    tok = self._dispatch_prefill_spec(
                        ids, slot, plan, n_pages
                    )
            if tracing._tracer is not None and tok.ndim:
                # the chunk's routing counters sit behind its token; they
                # land on the span once this step has waited for the
                # device anyway (a token fetch), never by a wait of
                # their own
                self._route_pending.append((span, tok))
            # the recompile sentinel's once-contract is per PROGRAM —
            # with buckets, a bucket IS a program, so single-bucket
            # engines keep the plain name and multi-bucket engines get
            # one sentinel key per bucket (one shared key would let a
            # recompile of bucket A mask a later recompile of bucket B)
            # (armed-only: the lookups are not disarmed-trivial args)
            if tracing._tracer is not None:
                tracing.note_compiles(
                    self._compile_note("prefill", n_pages),
                    self._prefill_bucket_compiles.get(n_pages),
                )
            self.pool.lengths[slot] = plan.start + plan.chunk_len
            chunks += 1
            if plan.final:
                # the slot's full prompt pages now hold canonical KV —
                # publish them for copy-free sharing by later admissions
                self.pool.register_prefix(h._lease, h.request.prompt_ids)
                if self.draft_pool is not None:
                    self.draft_pool.register_prefix(
                        h._dlease, h.request.prompt_ids
                    )
                if self._store is not None:
                    self._publish_prefixes(h)
                if self.role == "prefill":
                    # tier hand-off: pack the prompt's pages + the first
                    # token into a frame, park it in the outbox for the
                    # router, and retire the request here as MIGRATED —
                    # it continues on a decode-tier peer
                    try:
                        if faults.active():
                            faults.check(
                                "serve.kv_migrate",
                                path=h.request.request_id,
                            )
                        frame = self._pack_migration(
                            h, self._first_token(h, tok)
                        )
                    except faults.InjectedFault as e:
                        self._finish(h, RequestStatus.FAILED, error=e)
                        continue
                    self.outbox.append(frame)
                    self.migrated_out += 1
                    self._finish(h, RequestStatus.MIGRATED)
                    continue
                self.scheduler.prefill_finished(h)
                self._decoding_dirty = True
                self._emit(h, self._first_token(h, tok))
        return chunks

    def _first_token(self, h: RequestHandle, tok) -> int:
        """The final chunk's sampled token, read down to the host: a
        wait for the device (the chunk was only dispatched)."""
        span = (
            tracing._NULL_SPAN if tracing._tracer is None
            else tracing.span(
                "serve.first_token_fetch", request=h.request.request_id
            )
        )
        with span:
            # behind the token of an expert model ride its counters
            tok = int(np.asarray(tok)[0]) if tok.ndim else int(tok)
        self._land_route_stats()
        return tok

    def _land_route_stats(self) -> None:
        """Armed only, right after a token fetch: the chunks dispatched
        before it have run, so their counters are read without waiting
        and set on their (closed) ``serve.prefill_chunk`` spans."""
        for span, tok in self._route_pending:
            span.set(**_route_args(tok, 1))
        self._route_pending.clear()

    def _query_tile(self, w) -> Optional[int]:
        """How a call's attention cuts its ``w`` queries a row
        (``ops.paged_attention``): one walk for a tick's and a
        verify's; the kernel's tiles for a chunk's over a K/V pool,
        sized by the heads the model shows; None where the call gathers
        its bucket — the ``gather`` impl, and a latent pool's chunk
        (``MLAttention`` decodes the row's frames)."""
        width = kv_frame_width(self.pool.cache)
        if self._resolved_impl != "kernel" or (
            width is None and is_chunk(w)
        ):
            return None
        if not is_chunk(w):
            return w
        hq = self.model.config.num_heads
        hkv = getattr(self.model.config, "num_kv_heads", hq)
        return query_tiles(w, hq // hkv, hkv, width // hkv)[1]

    def _walk_pages(self, lengths, w, n_pages, gathered) -> dict:
        """A dispatch span's page counts, for rows of ``lengths`` cached
        tokens and ``w`` queries each. ``live_pages``: the pages the
        rows' lengths and write spans reach. ``fetched_pages``: the
        pages the attention goes over for them — the kernel's blocks of
        the live pages, whole (it copies a block's live pages only; its
        products span the block), once a tile of a chunk's queries (a
        tile fetches the row's prefix anew), by the kernel's own
        arithmetic; the ``gather`` impl gathers the bucket of every row
        it is handed, ``gathered`` pages, and so does a latent pool's
        chunk."""
        ps = self.pool.page_size
        k = block_pages(ps, self._token_bytes, n_pages)
        tq = self._query_tile(w)
        pages, blocks = tile_walk(
            np.asarray(lengths, np.int64), w, tq or w, ps, n_pages, k
        )
        return {
            "live_pages": int(pages[:, -1].sum()),
            "fetched_pages": gathered if tq is None else int(blocks.sum()) * k,
        }

    def _tick_pages(self, decoding, n_pages) -> dict:
        """A tick span's page counts: every decoding row's walk; the
        ``gather`` impl gathers every slot's bucket."""
        return self._walk_pages(
            [self.pool.lengths[slot] for slot, _ in decoding],
            1 if self.spec is None else self.spec.num_draft_tokens + 1,
            n_pages, self.config.num_slots * n_pages,
        )

    def _run_decode(self) -> int:
        """One decode tick over the decoding rows; returns how many
        rows it carried (0: no tick)."""
        if self._decoding_dirty:
            self._decoding_cached = self.scheduler.decoding()
            active = np.zeros(self.config.num_slots, bool)
            for slot, _ in self._decoding_cached:
                active[slot] = True
            self._active_cached = jnp.asarray(active)
            self._sampling_rows = sum(
                h.request.temperature > 0 for _, h in self._decoding_cached
            )
            self._decoding_dirty = False
        decoding = self._decoding_cached
        if not decoding:
            return 0
        self._decode_ticks += 1
        n_pages = self._tick_bucket(decoding)
        if self.spec is not None:
            return self._run_spec_tick(decoding, n_pages)
        # one jit call; toks/lengths/keys advance in-program for the
        # active rows, so the only per-tick host traffic is the sampled
        # tokens coming down
        # armed-only arg evaluation (PTD002): the steady-state tick is
        # the serving hot path — disarmed cost stays one is-None test
        span = (
            tracing._NULL_SPAN if tracing._tracer is None
            else tracing.span(
                "serve.decode_tick", active=len(decoding), n_pages=n_pages,
                sampling_rows=self._sampling_rows,
                **self._tick_pages(decoding, n_pages),
            )
        )
        with span:
            (
                self.pool.cache, nxt, self._toks, self._lengths,
                self._keys,
            ) = self._decode(
                self.params, self.pool.cache, self._pt, self._toks,
                self._lengths, self._keys, self._temps, self._top_ks,
                self._top_ps, self._active_cached, n_pages,
            )
        if tracing._tracer is not None:  # armed-only arg evaluation
            tracing.note_compiles(
                self._compile_note("decode", n_pages),
                self._decode_bucket_compiles.get(n_pages),
            )
        with tracing.span("serve.token_fetch"):
            # the one per-tick device sync: every sampled token comes down
            nxt = np.asarray(nxt)
        if tracing._tracer is not None and nxt.size > self.config.num_slots:
            span.set(**_route_args(nxt, self.config.num_slots))
            self._land_route_stats()
        fault_armed = faults.active()
        with tracing.span("serve.emit"):
            for slot, h in decoding:
                # the tick wrote this slot's token at lengths[slot];
                # mirror the in-program length advance, then judge it
                self.pool.lengths[slot] += 1
                if fault_armed:
                    try:
                        faults.check(
                            "serve.decode", path=h.request.request_id
                        )
                    except faults.InjectedFault as e:
                        self._finish(h, RequestStatus.FAILED, error=e)
                        continue
                self._emit(h, int(nxt[slot]))
        return len(decoding)

    def _dispatch_prefill(self, ids, slot, plan, n_pages):
        """One plain prefill-chunk dispatch; the donated pool buffer is
        rebound to its returned successor before anything reads it."""
        (
            cache, tok, self._toks, self._lengths, self._keys,
        ) = self._prefill(
            self.params, self.pool.cache, self._pt, ids,
            slot, plan.start, plan.chunk_len - 1, plan.final,
            self._toks, self._lengths, self._keys,
            self._temps, self._top_ks, self._top_ps, n_pages,
        )
        self.pool.cache = cache
        return tok

    def _dispatch_prefill_spec(self, ids, slot, plan, n_pages):
        """One fused target+draft prefill-chunk dispatch; both donated
        pool buffers rebind to their returned successors."""
        (
            cache, dcache, tok, self._toks, self._lengths, self._keys,
        ) = self._prefill_spec(
            self.params, self.spec.draft_params,
            self.pool.cache, self.draft_pool.cache,
            self._pt, self._dpt, ids,
            slot, plan.start, plan.chunk_len - 1, plan.final,
            self._toks, self._lengths, self._keys,
            self._temps, self._top_ks, self._top_ps, n_pages,
        )
        self.pool.cache = cache
        self.draft_pool.cache = dcache
        self.draft_pool.lengths[slot] = plan.start + plan.chunk_len
        return tok

    def _run_spec_tick(self, decoding, n_pages) -> int:
        """One fused draft+verify tick; emits 1..k+1 tokens/request."""
        span = (
            tracing._NULL_SPAN if tracing._tracer is None
            else tracing.span(
                "serve.spec_tick", active=len(decoding),
                k=self.spec.num_draft_tokens, n_pages=n_pages,
                sampling_rows=self._sampling_rows,
                **self._tick_pages(decoding, n_pages),
            )
        )
        with span:
            (
                self.pool.cache, self.draft_pool.cache, emit_acc,
                self._toks, self._lengths, self._keys,
            ) = self._spec_tick(
                self.params, self.spec.draft_params,
                self.pool.cache, self.draft_pool.cache,
                self._pt, self._dpt, self._toks, self._lengths,
                self._keys, self._temps, self._top_ks, self._top_ps,
                self._active_cached, n_pages,
            )
        if tracing._tracer is not None:  # armed-only arg evaluation
            tracing.note_compiles(
                self._compile_note("decode", n_pages),
                self._decode_bucket_compiles.get(n_pages),
            )
        with tracing.span("serve.token_fetch"):
            # ONE per-tick device sync: k+1 emit columns + the
            # accepted count packed into a single [S, k+2] fetch
            emit_acc = np.asarray(emit_acc)
        emit, acc = emit_acc[:, :-1], emit_acc[:, -1]
        k = self.spec.num_draft_tokens
        self.spec_verifies += 1
        fault_armed = faults.active()
        with tracing.span("serve.emit"):
            for slot, h in decoding:
                n = int(acc[slot]) + 1
                # mirror the in-program advances: the verify wrote k+1
                # entries but only a+1 became sequence; the rejected
                # tail sits beyond the accepted length where the next
                # tick's chunk write lands before anything attends it
                self.pool.lengths[slot] += n
                self.draft_pool.lengths[slot] += n
                self.spec_drafted += k
                self.spec_accepted += n - 1
                if fault_armed:
                    try:
                        faults.check(
                            "serve.decode", path=h.request.request_id
                        )
                    except faults.InjectedFault as e:
                        self._finish(h, RequestStatus.FAILED, error=e)
                        continue
                for j in range(n):
                    self._emit(h, int(emit[slot, j]))
                    if h.done:  # eos / max_new truncation retires it
                        break
        return len(decoding)

    # -- emission / retirement ---------------------------------------------
    def _emit(self, h: RequestHandle, token: int) -> None:
        now = self._clock()
        h.emit(token, now)
        req = h.request
        # continuing requests need no device write here: the decode tick
        # already advanced the slot's token/length/key rows in-program
        if req.eos_id is not None and token == req.eos_id:
            self._finish(h, RequestStatus.COMPLETED)
        elif len(h.tokens) >= req.max_new_tokens:
            self._finish(h, RequestStatus.COMPLETED)

    def _finish(
        self,
        h: RequestHandle,
        status: RequestStatus,
        error: Optional[BaseException] = None,
    ) -> None:
        h.status = status
        h.error = error
        h.finished_at = self._clock()
        if h.request.deadline_s is not None:
            self._n_deadlines -= 1
        self._decoding_dirty = True
        span = (
            tracing._NULL_SPAN if tracing._tracer is None
            else tracing.span(
                "serve.evict",
                request=h.request.request_id, status=status.value,
            )
        )
        with span:
            self.scheduler.release(h, self.pool, self.draft_pool)
        self.telemetry.record_done(h)
        if status is RequestStatus.FAILED:
            logger.warning(
                "serve: evicted request %s after fault: %s",
                h.request.request_id, error,
            )

    # -- admission-time slot setup ----------------------------------------
    def _configure_slot(self, h: RequestHandle) -> None:
        req = h.request
        lease = h._lease
        dpt_row = (
            h._dlease.page_row if h._dlease is not None
            else np.zeros(0, np.int32)
        )
        out = self._admit_rows(
            self._temps, self._top_ks, self._top_ps, self._keys,
            self._lengths, self._pt,
            self._dpt, h.slot,
            req.temperature,
            TOP_K_OFF if req.top_k is None else req.top_k,
            TOP_P_OFF if req.top_p is None else req.top_p,
            req.seed, lease.skip, lease.page_row, dpt_row,
        )
        (
            self._temps, self._top_ks, self._top_ps, self._keys,
            self._lengths, self._pt,
        ) = out[:6]
        if self._dpt is not None:
            self._dpt = out[6]
