"""Paged attention: a tick, a speculative verify and a prompt chunk
attend over K/V where the page pool holds it — the compute-side
completion of the paged KV pool (serve/kv_slots).

PR 11 made the PAGE the allocation unit but left the compute contract
dense: every decode tick gathered the live slots' pages into a transient
``[S, max_len]`` view, ran the unchanged dense decode, and scattered one
token back — on a bandwidth-bound chip that roughly doubles HBM traffic
per token (gather + attention read) and sizes the transient peak by
``max_len``, not by what is live. This module is the PagedAttention
design (vLLM, arXiv 2309.06180) expressed with the repo's own blocked
online-softmax machinery (ops/flash_attention.py):

* the decode-attention primitive takes the pooled KV frames
  ``[num_pages + 1, page_size, Hkv * D]`` (frame 0 the reserved null
  page; with a leading ``[L]`` and a ``layer`` index when the layers
  are scanned), per-request page tables ``[B, n_pages]`` and per-row
  lengths, and computes ``[B, W, Hq, D]`` attention for W queries per
  row (W = 1 for the decode tick, W = k+1 for the fused speculative
  verify, W = the chunk for a prompt chunk's one row) with ragged
  lengths masked INSIDE the op — no caller-side dense view;
* the engine installs a :class:`PagedView` (the adapter object) around
  its jitted decode and prefill programs;
  ``ops.attention.decode_cache`` writes new
  K/V through :func:`paged_write` (a per-page scatter of only the W
  deliberately-written positions — dropped entirely for inactive rows)
  and ``ops.attention.attention`` dispatches here — so ``models/``
  attention code stays ONE implementation.

Two implementations, selected by :func:`set_paged_attention_impl`
(default ``"auto"``), and a reference beside them:

* ``"gather"`` — materialize the (bucket-sliced, NOT max_len-wide)
  pages into a per-row dense slab inside the op and run the UNCHANGED
  ``dot_product_attention`` math. BIT-IDENTICAL to attention over a
  dense cache by the zero-tail argument (masked tail keys contribute
  exact 0.0 to every reduction; live keys occupy the same leading
  positions — verified empirically per dtype in
  tests/test_paged_attention.py), so the engine's pinned
  solo-``generate`` parity holds to the bit. The only impl that takes
  int8 pools.
* ``"kernel"`` — the Pallas TPU kernel. It walks what a row OWNS, many
  pages a step: the grid is over groups of rows, and each row runs as
  many steps as it has BLOCKS of live pages (``row_walk``: the pages
  its prefetched length and the call's W queries reach, in blocks of
  ``block_pages`` pages, 512 tokens at the serving cells' frames) — a
  slot that is not decoding (``keep`` False) runs none and reads zeros,
  and a table wider than its rows costs nothing. The pools are operands
  in whatever memory they lie in (``pl.ANY``): a block's live page
  frames are copied one ``make_async_copy`` each, through the prefetched
  table (and layer, for a stacked leaf), into one of two VMEM buffers
  ``[k * ps, Hkv * D]``, and the next block's copies — the row's next
  block, or the first block of the next row that has any — are started
  before the current block's products, so the fetch hides behind the
  arithmetic. A block is multiplied as it lies, lane-dense: the queries
  of ALL kv heads form one block-diagonal ``[Hkv * rows, Hkv * D]``
  operand (head ``h``'s rows hold its queries in lanes ``[h * D, (h + 1)
  * D)``, zeros elsewhere), so scores are ONE ``[Hkv * rows, D'] x [D',
  k * ps]`` product and values one ``[.., k * ps] x [k * ps, Hkv * Dv]``
  a block, every frame passing the multiplier once in whole 128-lane
  tiles; head ``h``'s output is lanes ``[h * Dv, (h + 1) * Dv)`` of its
  own rows. The partial last block masks by position (causal,
  ``window``); its unfetched tail keeps finite stale frames that the
  masked scores weigh by an exact 0.0, so no page past a row's length
  is ever read. The online-softmax carry lives in VMEM scratch.
  ``interpret=True`` off-TPU, like every Pallas kernel in this repo.
  That body is a tick's and a verify's: a handful of queries a row. A
  call of ``_CHUNK_QUERIES`` queries a row or more over a K and a V
  pool (a prompt chunk) takes the QUERY-TILED body instead, told from
  the call's shapes alone (``_paged_kernel_call``): the same walk
  — the row's own blocks of live pages through the prefetched table,
  copied by hand from the pool where it lies, double-buffered — with
  the grid over tiles of ``query_tiles`` positions; a tile walks the
  blocks up to its own last query, multiplies each fetched block one
  128-lane group of heads at a time (one head of 128, two of 64 as a
  block-diagonal pair) against that group's tile of query rows, and
  compares positions only in the blocks its own queries fall in (or a
  window's edge crosses). No bucket-wide array and no score matrix
  reaches HBM. Its op is named ``paged_prefill``:
  the ticks' roofline readers sum the device time of every op named
  ``paged_attention``. (A latent frame's chunk does not come here:
  ``MLAttention`` decodes the row's frames, :func:`gathered_rows`.)

``"auto"`` resolves to ``"kernel"`` on TPU and ``"gather"`` elsewhere:
the gather impl is the provably-exact CPU/CI path, and on the chip the
kernel is the point of this module. :func:`paged_attention_reference`
(one page a ``lax.scan`` step, an online-softmax carry) is selected by
nothing: it is the plain float form the tests hold both against. What a
v5e showed (jax 0.9.0, libtpu 0.0.34, chip_smoke.py): the kernel
compiles in a few seconds and matches ``"gather"`` — f32 to ~2e-6,
bf16 to ~5e-3 of the output scale — for 16/16 heads of 64 and 32/8
heads of 128, W = 1 and 5, with and without a window, at page sizes
4-32 (f32) and 8-32 (bf16); smaller pages were not tried (the
single-page body of PR 21; the blocked walk of PR 28 was compared with
``"gather"`` at the serving cells' shapes by
``scripts/paged_kernel_bench.py``, bf16 to ~4e-3). Every serving cell
of the benchmark serves through it; PERF.md §6 has its timings.

int8 KV caches (``kv_cache_quantize="int8"``): payload + per-token
scale pools ride together (:class:`PagedKVQuant`); ``"gather"``
dequantizes per page with decode_cache's exact formula. The kernel does
not take quantized pools and REFUSES them by name
(:func:`refuse_kernel_for`) — on a TPU an int8 cache needs
``set_paged_attention_impl("gather")`` said out loud, never a quiet
reroute that would hide from a measurement what actually ran.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_tpu.ops.flash_attention import _mxu_dot

_NEG_INF = -1e30  # finite, like flash_attention: no (-inf) - (-inf) NaN


# --------------------------------------------------------------------------
# the engine-facing adapter: a trace-scoped view of the page pool
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PagedView:
    """What the attention layers need to decode in place over the pool.

    Installed by the serving engine around the traced body of its
    decode/verify programs (:func:`paged_view`); consumed by
    ``ops.attention.decode_cache`` (per-page writes) and
    ``ops.attention.attention`` (dispatch to :func:`paged_attention`) —
    the models themselves never see it, which is how ``models/``
    attention code stays one implementation.

    ``page_tables`` is bucket-sliced to a STATIC width by the caller
    (serve/engine.py's length buckets); ``keep`` gates writes per row —
    False rows (free / mid-prefill slots) drop their writes entirely,
    the same strictly-stronger-than-masking invariant scatter_kv
    established.
    """

    page_tables: jnp.ndarray  # [B, n_pages] int32, bucket-sliced
    keep: jnp.ndarray         # [B] bool — write gate per row
    page_size: int
    # the plane of a STACKED leaf ([L, P1, ps, ...], layers under
    # nn.scan) this layer reads and writes: a traced int32 scalar the
    # layer loop hands down (:func:`paged_layer`); None for per-layer
    # leaves ([P1, ps, ...], an unrolled stack)
    layer: Optional[jnp.ndarray] = None


_VIEW: Optional[PagedView] = None


@contextlib.contextmanager
def paged_view(view: PagedView):
    """Install ``view`` for the duration of a traced model apply.

    Trace-scoped, not run-scoped: the engine's jitted program bodies
    wrap exactly the ``model.apply`` that should decode over the pool
    (the speculative program's draft scan stays dense and runs OUTSIDE
    the with-block of its verify)."""
    global _VIEW
    prev = _VIEW
    _VIEW = view
    try:
        yield view
    finally:
        _VIEW = prev


def active_view() -> Optional[PagedView]:
    return _VIEW


def paged_layer(layer):
    """Inside a layer loop's body (models/scan.py): the active view
    with ``layer`` naming this iteration's plane of the stacked leaves."""
    return paged_view(dataclasses.replace(_VIEW, layer=layer))


# a call of this many queries a row or more is a prompt chunk; under it
# a tick's one and a speculative verify's k + 1. 16: the least tile of
# positions the many-query body cuts for every group size
_CHUNK_QUERIES = 16


def is_chunk(w: int) -> bool:
    """Whether a call of ``w`` queries a row is a prompt chunk (the
    kernel's query-tiled body) and not a tick or a speculative verify
    (its block-diagonal one)."""
    return w >= _CHUNK_QUERIES


@contextlib.contextmanager
def gathered_rows(pages):
    """The active view's rows of a pool leaf, dense — ``[B, n_pages *
    page_size, F]``, one gather through the bucket-sliced tables (plane
    ``layer`` of a stacked leaf), what the ``"gather"`` impl reads —
    with the view lifted while the caller attends over them. For a
    caller whose attention over the cached positions is not the pool's
    own form: a latent chunk decodes its latents
    (``models/deepseek_v3.py``)."""
    global _VIEW
    view = _VIEW
    B, n = view.page_tables.shape
    out = pages[_plane(view.layer, view.page_tables.reshape(-1))]
    _VIEW = None
    try:
        yield out.reshape(B, n * out.shape[1], out.shape[2])
    finally:
        _VIEW = view


class PagedKVQuant(NamedTuple):
    """An int8 page pool + its per-token scale pool, moving as one.

    ``decode_cache`` returns this pair (instead of a dequantized dense
    buffer) in paged mode; models pass it through to ``attention``
    untouched, and the dispatcher dequantizes per page with the same
    ``int8 -> f32 * scale -> dtype`` formula the dense path used.
    """

    pages: jnp.ndarray   # [P1, ps, H * D] int8
    scale: jnp.ndarray   # [P1, ps, H] f32
    dtype: jnp.dtype     # the compute dtype attention should see


class PagedPrefix(NamedTuple):
    """The VALUE pool of a latent cache: the first ``width`` lanes of
    every frame of ``pages``, which is the key pool itself (multi-head
    latent attention caches one frame a token; its scores run over the
    whole frame and its values are the frame's leading lanes).
    ``ops.attention.decode_latent_cache`` returns it in paged mode;
    every impl reads the one pool once and never slices it."""

    pages: jnp.ndarray   # the key pool, [.., P1, ps, F]
    width: int           # lanes of a frame that are the value


# --------------------------------------------------------------------------
# per-page writes
# --------------------------------------------------------------------------


def paged_write(pool, new, page_tables, write_pos, keep, layer=None):
    """Scatter ``new`` rows into the page pool through the page table.

    ``pool`` is ``[num_pages + 1, page_size, F]`` (``layer`` None) or
    the stacked ``[L, num_pages + 1, page_size, F]`` with ``layer`` its
    plane; ``new`` is ``[B, W, ...]`` with ``F`` elements a position:
    row ``b``'s W entries land at buffer positions ``write_pos[b] ..
    write_pos[b] + W - 1``, each mapped to frame ``page_tables[b, pos //
    page_size]``, row ``pos % page_size``. ONE scatter into the leaf
    where it lies — no reshape, slice or restack of it — so a donated
    pool is updated in place. ``keep[b]`` False redirects the row's
    frames out of bounds so ``mode="drop"`` discards them — free and
    mid-prefill rows never touch the pool, the invariant
    ``serve.kv_slots.scatter_kv`` established (a kept row's positions
    sit inside its privately-owned span by the pool's CoW admission
    discipline, so a refcount>1 page can never be written).

    Only ever traced inside the engine's jitted programs (it is called
    from ``decode_cache`` under the model apply those programs trace) —
    the eager form would be the exact dispatch-cost bug PTD004 exists
    for, which is why the lint fixture corpus carries a twin of this
    helper.
    """
    P1, ps, F = pool.shape[-3:]
    B, W = new.shape[0], new.shape[1]
    pos = write_pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
    # positions beyond the (bucket-sliced) table clamp; such rows are
    # always keep=False, so the clamped index is dropped below anyway
    page = jnp.take_along_axis(page_tables, pos // ps, axis=1)
    page = jnp.where(keep[:, None], page, P1)          # OOB -> drop
    idx = (page.reshape(-1), (pos % ps).reshape(-1))
    if layer is not None:
        idx = (layer,) + idx
    upd = new.astype(pool.dtype).reshape(B * W, F)
    return pool.at[idx].set(  # ptdlint: disable=PTD004
        upd, mode="drop",
    )  # fused scatter: traced only inside the engine's jitted programs
    # (cross-module, so the per-module lint closure cannot see the jit)


# --------------------------------------------------------------------------
# implementation dispatch
# --------------------------------------------------------------------------

_IMPLS = ("auto", "gather", "kernel")
_IMPL = "auto"


def set_paged_attention_impl(impl: str) -> None:
    """Select the paged-attention backend (see module docstring).

    Mirrors ``ops.attention.set_attention_impl``: jit caches do not key
    on this flag, so switching drops them and already-compiled decode
    programs retrace with the new backend.
    """
    _check_impl(impl)
    global _IMPL
    if impl == _IMPL:
        return
    # drop jit caches only when the RESOLVED backend actually changes —
    # pinning "auto" to the backend it already resolves to must not
    # force every compiled program (and the serve engine's
    # compiled-once-per-bucket ledger) through a spurious retrace
    changed = (
        resolve_paged_attention_impl(impl)
        != resolve_paged_attention_impl(_IMPL)
    )
    _IMPL = impl
    if changed:
        jax.clear_caches()


def _check_impl(impl: str) -> None:
    if impl not in _IMPLS:
        raise ValueError(
            f"unknown paged-attention impl {impl!r}: one of "
            f"{', '.join(map(repr, _IMPLS))}"
        )


def get_paged_attention_impl() -> str:
    return _IMPL


def resolve_paged_attention_impl(impl: Optional[str] = None) -> str:
    """The concrete backend an ``impl`` (default: the global flag)
    resolves to on this backend — the engine consults it once at
    construction and refuses to tick under another."""
    impl = impl or _IMPL
    _check_impl(impl)
    if impl != "auto":
        return impl
    return "kernel" if jax.default_backend() == "tpu" else "gather"


def refuse_kernel_for(*, quantized: bool, page_size: int = 8) -> None:
    """Raise for the pools the ``"kernel"`` impl cannot serve, naming
    the way out — never a quiet reroute to another impl, which would
    hide from a measurement what actually ran."""
    if quantized:
        raise ValueError(
            "the paged-attention kernel takes floating-point pools "
            "only; an int8 KV cache (kv_cache_quantize='int8') needs "
            "set_paged_attention_impl('gather') — on a TPU 'auto' "
            "resolves to 'kernel'"
        )
    if page_size % 8 and not _interpret():
        raise ValueError(
            f"the paged-attention kernel copies each page frame to a "
            f"whole sublane tile of its block: on a TPU page_size must "
            f"be a multiple of 8, got {page_size} — pass a larger "
            f"page_size or set_paged_attention_impl('gather')"
        )


def _unpack(kv):
    if isinstance(kv, PagedKVQuant):
        return kv.pages, kv.scale, kv.dtype
    return kv, None, None


def _plane(layer, frames):
    """Index of ``frames`` in a pool leaf: straight into the leaf when
    it is per-layer, into plane ``layer`` when it is stacked — one
    gather either way, never a slice of the plane first."""
    return (frames,) if layer is None else (layer, frames)


def paged_attention(
    q: jnp.ndarray,   # [B, W, Hq, D]
    k_pages,          # [P1, ps, Hkv * D] or PagedKVQuant
    v_pages,          # [P1, ps, Hkv * Dv], PagedKVQuant or PagedPrefix
    *,
    page_tables: jnp.ndarray,  # [B, n_pages] int32 (bucket-sliced)
    lengths: jnp.ndarray,      # [B] int32 — tokens cached BEFORE this call
    layer=None,                # int32 scalar: pools are [L, P1, ps, ...]
    keep: Optional[jnp.ndarray] = None,  # [B] bool: rows that decode
    scale: Optional[float] = None,
    window: Optional[int] = None,
    impl: Optional[str] = None,
) -> jnp.ndarray:
    """Decode attention over the page pool; returns [B, W, Hq, Dv].

    Query ``j`` of row ``b`` sits at absolute position
    ``lengths[b] + j`` and attends buffer positions ``<= lengths[b] + j``
    (``window`` further restricts to the sliding band, HF convention:
    a key exactly ``window`` back is masked) — the same per-row causal
    contract ``dot_product_attention``'s ``[B]`` ``q_offset`` form
    implements, with the new tokens' own K/V expected ALREADY WRITTEN
    into the pool (``decode_cache`` writes before it attends, as the
    dense path always did). Unused table entries hold null page 0;
    they back positions ``>= lengths[b] + W`` and are causally masked,
    so the null page's contents are unobservable (pinned by test).

    With ``layer`` the pools are the STACKED leaves of a scanned model
    and every impl reads plane ``layer`` of them in place. ``keep``
    (the engine's write gate) names the rows that decode: the kernel
    walks no page of a row it marks False and returns zeros for it;
    ``"gather"`` ignores it, and the caller discards such rows anyway.

    The case is told from the shapes handed in: the kv heads are the
    key frame's width over ``q``'s head size, the value's head size the
    value frame's width over them, and it may differ from the key's. A
    :class:`PagedPrefix` value (a latent cache: one frame a token,
    shared by every query head, values its leading lanes) reads the key
    pool alone.
    """
    k_pages, k_scale, kdt = _unpack(k_pages)
    # a latent cache hands ONE pool: its values are a prefix of the
    # key's frame, and score and value widths differ
    dv = None
    if isinstance(v_pages, PagedPrefix):
        if v_pages.pages is not k_pages or k_scale is not None:
            raise ValueError(
                "a PagedPrefix value must be a prefix of the (floating-"
                "point) key pool it is handed with"
            )
        dv, v_pages, v_scale = v_pages.width, None, None
    else:
        v_pages, v_scale, _ = _unpack(v_pages)
    B, W, Hq, D = q.shape
    if k_pages.ndim != (3 if layer is None else 4):
        raise ValueError(
            f"the pool must be [P1, ps, Hkv * D], or [L, P1, ps, Hkv * D] "
            f"with its layer; got {k_pages.shape} and layer "
            f"{'None' if layer is None else 'given'}"
        )
    P1, ps, F = k_pages.shape[-3:]
    if F % D:
        raise ValueError(
            f"head_dim mismatch: q {D} does not divide the pool's {F}"
        )
    Hkv = F // D
    if Hq % Hkv:
        raise ValueError(
            f"query heads {Hq} not a multiple of kv heads {Hkv}"
        )
    if dv is None:
        dv = v_pages.shape[-1] // Hkv
    elif Hkv != 1 or not 0 < dv <= D:
        raise ValueError(
            f"a PagedPrefix value of {dv} lanes needs one kv head of at "
            f"least that many; the pool's frame is {Hkv} x {D}"
        )
    if page_tables.ndim != 2 or page_tables.shape[0] != B:
        raise ValueError(
            f"page_tables must be [batch, n_pages] = [{B}, *], got "
            f"{page_tables.shape}"
        )
    if lengths.shape != (B,):
        raise ValueError(f"lengths must be [{B}], got {lengths.shape}")
    if window is not None and window <= 0:
        raise ValueError(f"window must be positive, got {window}")
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    impl = resolve_paged_attention_impl(impl)
    if impl == "kernel":
        refuse_kernel_for(quantized=k_scale is not None, page_size=ps)
    if impl == "gather":
        return _paged_gather(
            q, k_pages, v_pages, page_tables, lengths, scale, window,
            k_scale, v_scale, kdt, layer, dv,
        )
    return _paged_kernel_call(
        q, k_pages, v_pages, page_tables, lengths, keep, layer, scale,
        window, dv,
    )


# --------------------------------------------------------------------------
# "gather": bucket-wide dense slab + the unchanged dense attention math
# --------------------------------------------------------------------------


def _take_frames(pages, scale_pages, frames, layer, D, dtype):
    """``frames`` ([N] ids) of a pool -> ``[N, ps, Hkv, D]``, dequantized
    with decode_cache's exact formula when scales ride."""
    idx = _plane(layer, frames)
    out = pages[idx]                                  # [N, ps, Hkv * D]
    out = out.reshape(out.shape[:2] + (-1, D))
    if scale_pages is not None:
        sc = scale_pages[idx][..., None]              # [N, ps, Hkv, 1]
        out = (out.astype(jnp.float32) * sc).astype(dtype)
    return out


def _paged_gather(q, k_pages, v_pages, tables, lengths, scale, window,
                  k_scale, v_scale, kdt, layer, dv):
    """The exact impl: materialize the bucket slab, run the SAME
    ``dot_product_attention`` the dense engine path ran. Masked tail
    keys contribute exact zeros to every reduction (the zero-tail
    argument), so the output is bitwise the pre-paged path's."""
    from pytorch_distributed_tpu.ops.attention import dot_product_attention

    B, n = tables.shape
    D = q.shape[-1]

    def dense(pages, scales, d):
        out = _take_frames(
            pages, scales, tables.reshape(-1), layer, d, kdt or q.dtype
        )
        return out.reshape((B, n * out.shape[1]) + out.shape[2:])

    k = dense(k_pages, k_scale, D)
    v = k[..., :dv] if v_pages is None else dense(v_pages, v_scale, dv)
    return dot_product_attention(
        q, k, v, causal=True, q_offset=lengths, scale=scale, window=window,
    )


# --------------------------------------------------------------------------
# the reference: a pure-jnp scan over pages, online softmax
# --------------------------------------------------------------------------


def paged_attention_reference(
    q, k_pages, v_pages, *, page_tables, lengths, layer=None,
    scale: Optional[float] = None, window: Optional[int] = None,
    value_dim=None,
):
    """The float reference the tests compare the impls with: one page
    of K/V per ``lax.scan`` step, online-softmax carry.

    Per step it touches ONE page frame per row (a ``[B, ps, Hkv, D]``
    transient), never a ``[B, n*ps]`` dense slab. Reductions are
    reassociated page-by-page (rescale by ``exp(m_prev - m_new)``), so
    outputs match the dense path to last-ulp tolerance per dtype, not
    bitwise — the gather impl is the bit-exact one. Floating-point
    pools only, stored as ``paged_attention`` takes them (``layer``
    names the plane of a stacked leaf). ``v_pages`` None reads the
    values off the key frame's first ``value_dim`` lanes (a latent
    cache).
    """
    B, W, Hq, D = q.shape
    ps, Hkv = k_pages.shape[-2], k_pages.shape[-1] // D
    G = Hq // Hkv
    dv = value_dim or v_pages.shape[-1] // Hkv
    n = page_tables.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, W, Hkv, G, D)
    qpos = lengths[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]

    def page(pages, i, d):                    # -> [B, ps, Hkv, d]
        out = pages[_plane(layer, page_tables[:, i])]
        return out.reshape(out.shape[:2] + (-1, d))

    def body(carry, i):
        m, l, acc = carry
        k = page(k_pages, i, D)
        v = k[..., :dv] if v_pages is None else page(v_pages, i, dv)
        s = jnp.einsum(
            "bwkgd,bpkd->bwkgp", qg, k,
            preferred_element_type=jnp.float32,
        ) * scale                                  # [B, W, Hkv, G, ps]
        kpos = i * ps + jnp.arange(ps, dtype=jnp.int32)
        keep = qpos[:, :, None] >= kpos[None, None, :]   # [B, W, ps]
        if window is not None:
            keep = keep & (qpos[:, :, None] - kpos[None, None, :] < window)
        s = jnp.where(keep[:, :, None, None, :], s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bwkgp,bpkd->bwkgd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    # page 0 always holds a live key per row (kpos 0 <= qpos), so the
    # carry's m leaves _NEG_INF on the first step and the masked
    # exp(_NEG_INF - m) terms underflow to exact 0.0 ever after
    m0 = jnp.full((B, W, Hkv, G), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, W, Hkv, G), jnp.float32)
    acc0 = jnp.zeros((B, W, Hkv, G, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), jnp.arange(n), length=n
    )
    safe = jnp.where(l > 0, l, 1.0)
    out = (acc / safe[..., None]).astype(q.dtype)
    return out.reshape(B, W, Hq, dv)


# --------------------------------------------------------------------------
# "kernel": Pallas, a row's live pages fetched a block at a time
# --------------------------------------------------------------------------

# what ONE pool's double buffer may take of VMEM, and the most tokens a
# block spans: the table of scripts/paged_kernel_bench.py (PERF.md §6,
# PR 28) chose both
_BLOCK_VMEM_BYTES = 2 << 20
_BLOCK_MAX_TOKENS = 512


def block_pages(page_size: int, token_bytes: int, n_pages: int) -> int:
    """Pages the kernel fetches and multiplies a step (a BLOCK), derived
    from what the call shows: two blocks of the widest pool's frames
    (``token_bytes`` a token) fit the VMEM budget, a block spans at most
    ``_BLOCK_MAX_TOKENS`` and never more than the table's ``n_pages``;
    a power of two."""
    k = min(
        _BLOCK_VMEM_BYTES // (2 * page_size * token_bytes),
        _BLOCK_MAX_TOKENS // page_size,
        n_pages,
    )
    return 1 << max(k, 1).bit_length() - 1


def row_walk(lengths, w: int, page_size: int, n_pages: int, k: int):
    """What the kernel walks of each row: ``(pages, blocks)``, the pages
    a row of ``lengths`` cached tokens and ``w`` queries reaches (never
    past the table) and the blocks of ``k`` pages it fetches them in.
    Plain arithmetic over a numpy or a jax array: the kernel's wrapper
    and the engine's ``fetched_pages`` share it."""
    pages = (-(-(lengths + w) // page_size)).clip(None, n_pages)
    return pages, -(-pages // k)


def _kernel_body(lengths_ref, pages_ref, next_ref, tables_ref, *refs,
                 sm_scale, page_size, k, hkv, g, w, d, dv, window,
                 n_pools, stacked):
    # after the prefetched scalars (a stacked pool's plane among them):
    # the queries, the pools where they lie, the output, then scratch
    refs = list(refs)
    layer_ref = refs.pop(0) if stacked else None
    q_ref, o_ref = refs.pop(0), refs.pop(n_pools)
    pools, bufs = refs[:n_pools], refs[n_pools:2 * n_pools]
    sems, slot_ref, q_all_ref, acc_ref, m_ref, l_ref = refs[2 * n_pools:]
    # a latent cache: the values are the key frame's leading lanes
    k_buf, v_buf = bufs[0], bufs[-1]
    # batch rows a grid step; g * w query rows a kv head, sublane-padded
    group, _, rows, _ = q_ref.shape
    n_rows = pl.num_programs(0) * group
    step = pl.program_id(0)
    kps = k * page_size

    def copies(row, blk, slot, wait):
        """Start (or wait for) the copies of block ``blk`` of ``row``:
        one per LIVE page frame and pool, from where the frame lies in
        the pool to its place in buffer ``slot``. Pages past the row's
        last are neither fetched nor waited for."""
        first = blk * k

        def page(j, carry):
            frame = tables_ref[row, first + j]
            at = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            for p in range(n_pools):
                src = (
                    pools[p].at[layer_ref[0], frame] if stacked
                    else pools[p].at[frame]
                )
                copy = pltpu.make_async_copy(
                    src, bufs[p].at[slot, at], sems.at[p, slot]
                )
                copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(
            0, jnp.minimum(k, pages_ref[row] - first), page, 0
        )

    @pl.when(step == 0)
    def _prologue():
        # a block's unfetched tail keeps what the buffer held: masked
        # scores weigh it by an exact 0.0, so it has to be finite —
        # zeros now, live frames fetched earlier ever after
        def fill(j, carry):
            at = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            for buf in bufs:
                for slot in range(2):
                    buf[slot, at, :] = jnp.zeros(
                        (page_size, buf.shape[2]), buf.dtype
                    )
            return carry

        jax.lax.fori_loop(0, k, fill, 0)
        # the queries of all kv heads as ONE block-diagonal operand:
        # head h's rows hold its queries in lanes [h * D, (h + 1) * D),
        # the frame's own layout, and zeros elsewhere, for good
        q_all_ref[:] = jnp.zeros_like(q_all_ref)
        slot_ref[0] = 0

        @pl.when(next_ref[0] < n_rows)
        def _first():
            copies(next_ref[0], 0, 0, wait=False)

    # rows are ordered (kv head, query j, group member): row r of a
    # head's ``rows`` is query r // g. Counted with compares because
    # Mosaic has no vector integer divide
    row_of = jax.lax.broadcasted_iota(jnp.int32, (rows, kps), 0)
    j = jnp.zeros_like(row_of)
    for t in range(1, w):
        j = j + (row_of >= t * g).astype(jnp.int32)
    j = jnp.concatenate([j] * hkv, axis=0)            # [hkv * rows, kps]
    col = jax.lax.broadcasted_iota(jnp.int32, j.shape, 1)

    def row(r, carry):
        b = step * group + r
        n_blocks = -(-pages_ref[b] // k)

        @pl.when(n_blocks == 0)
        def _not_decoding():
            o_ref[r] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

        @pl.when(n_blocks > 0)
        def _walk():
            for h in range(hkv):
                q_all_ref[h * rows:(h + 1) * rows, h * d:(h + 1) * d] = (
                    q_ref[r, h]
                )
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            qpos = lengths_ref[b] + j

            def block(i, slot):
                # the block after this one — the row's next, or the
                # first of the next row that has any — is on its way
                # while this one multiplies
                last = i + 1 == n_blocks
                nrow = jnp.where(last, next_ref[b + 1], b)

                @pl.when(nrow < n_rows)
                def _prefetch():
                    copies(
                        nrow, jnp.where(last, 0, i + 1), 1 - slot,
                        wait=False,
                    )

                copies(b, i, slot, wait=True)
                kpos = i * kps + col
                keep = qpos >= kpos
                if window is not None:
                    keep = jnp.logical_and(keep, qpos - kpos < window)
                # ONE product against the block as it lies, [k * ps,
                # Hkv * D] lane-dense: the zeros of the block-diagonal
                # queries keep the heads apart, and every frame passes
                # the multiplier once, in whole tiles
                s = _mxu_dot(q_all_ref[:], k_buf[slot], 1, 1) * sm_scale
                s = jnp.where(keep, s, _NEG_INF)        # [hkv * rows, kps]
                m_prev = m_ref[:, :1]                   # [.., 1] (lanes
                l_prev = l_ref[:, :1]                   #  replicated)
                m_cur = jnp.max(s, axis=-1, keepdims=True)
                m_new = jnp.maximum(m_prev, m_cur)
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
                v = v_buf[slot, :, :hkv * dv]
                acc_ref[:] = acc_ref[:] * alpha + _mxu_dot(
                    p.astype(v.dtype), v, 1, 0
                )
                m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
                l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)
                return 1 - slot

            # as many steps as the row has blocks
            slot_ref[0] = jax.lax.fori_loop(0, n_blocks, block, slot_ref[0])
            l = l_ref[:, :1]
            safe = jnp.where(l > 0, l, 1.0)
            for h in range(hkv):  # head h's own lanes of its own rows
                at = slice(h * rows, (h + 1) * rows)
                o_ref[r, h] = (
                    acc_ref[at, h * dv:(h + 1) * dv] / safe[at]
                ).astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, group, row, 0)


# --------------------------------------------------------------------------
# "kernel", many queries a row: the same walk, tiles of query rows
# --------------------------------------------------------------------------

# query rows a tile multiplies a fetched block by, a lane group: the
# table of scripts/paged_kernel_bench.py --chunks (PERF.md §6, PR 30)
# chose it
_TILE_ROWS = 1024


def query_tiles(w: int, g: int, hkv: int, d: int):
    """How the many-query body cuts a call, from its shapes: ``(hpg,
    tq, nt)``. ``hpg`` kv heads share a lane group — whole 128-lane
    tiles of a frame: two heads of 64, one of 128 — ``tq`` query
    positions make a tile (about ``_TILE_ROWS`` rows of a lane group,
    whole sublane tiles, never more than the call has) and ``nt`` tiles
    cover the ``w`` queries."""
    hpg = max(
        (h for h in range(1, hkv + 1) if hkv % h == 0 and h * d <= 128),
        default=1,
    )
    whole = 16 // math.gcd(g, 16)                # tq * g: whole tiles
    tq = max(min(_TILE_ROWS // (hpg * g), w), 1)
    tq = -(-tq // whole) * whole
    return hpg, tq, -(-w // tq)


def tile_walk(lengths, w: int, tq: int, page_size: int, n_pages: int,
              k: int):
    """``row_walk`` for each query tile of rows of ``lengths`` cached
    tokens and ``w`` queries cut ``tq`` a tile: ``(pages, blocks)``,
    each ``[B, tiles]`` — the pages tile ``t``'s last query reaches (a
    tile walks no block past its own causal reach, and none past the
    row's) and its blocks of them. A row's prefix is fetched once a
    tile."""
    seen = np.minimum((np.arange(-(-w // tq), dtype=np.int32) + 1) * tq, w)
    return row_walk(lengths[:, None] + seen[None, :], 0, page_size, n_pages, k)


def _lanes(x, n: int):
    """``x`` (``[rows, 128]``, a row's lanes all alike) at ``n`` lanes:
    whole tiles side by side, no move across lanes, where ``n`` is a
    multiple of 128."""
    if n % 128:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return x if n == 128 else jnp.concatenate([x] * (n // 128), axis=1)


def _tiled_body(lengths_ref, pages_ref, next_ref, tables_ref, *refs,
                sm_scale, page_size, k, hkv, hpg, g, tq, d, dv, window,
                stacked):
    # the operands of ``_kernel_body`` over a K and a V pool; one grid
    # step is one TILE of one row's queries, ``pages_ref`` the pages
    # that tile's last query sees
    refs = list(refs)
    layer_ref = refs.pop(0) if stacked else None
    q_ref, *pools, o_ref, k_buf, v_buf = refs[:6]
    sems, slot_ref, *packed, acc_ref, m_ref, l_ref = refs[6:]
    bufs = (k_buf, v_buf)
    rows = q_ref.shape[2]                          # tq * g, a kv head
    n_lg = hkv // hpg
    b, t = pl.program_id(0), pl.program_id(1)
    n_rows, nt = pl.num_programs(0), pl.num_programs(1)
    kps = k * page_size

    def copies(row, tile, blk, slot, wait):
        """``_kernel_body``'s: the live page frames of block ``blk`` of
        what tile ``tile`` of ``row`` sees, to buffer ``slot``."""
        first = blk * k

        def page(j, carry):
            frame = tables_ref[row, first + j]
            at = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            for p, (pool, buf) in enumerate(zip(pools, bufs)):
                src = (
                    pool.at[layer_ref[0], frame] if stacked
                    else pool.at[frame]
                )
                copy = pltpu.make_async_copy(
                    src, buf.at[slot, at], sems.at[p, slot]
                )
                copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(
            0, jnp.minimum(k, pages_ref[row * nt + tile] - first), page, 0
        )

    @pl.when(jnp.logical_and(b == 0, t == 0))
    def _prologue():
        # finite for good, as in ``_kernel_body``: a block's unfetched
        # tail is weighed by an exact 0.0
        def fill(j, carry):
            at = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            for buf in bufs:
                for slot in range(2):
                    buf[slot, at, :] = jnp.zeros(
                        (page_size, buf.shape[2]), buf.dtype
                    )
            return carry

        jax.lax.fori_loop(0, k, fill, 0)
        for ref in packed:  # the zeros between a lane group's heads
            ref[:] = jnp.zeros_like(ref)
        slot_ref[0] = 0

        @pl.when(next_ref[0] < n_rows)
        def _first():
            copies(next_ref[0], 0, 0, 0, wait=False)

    n_blocks = -(-pages_ref[b * nt + t] // k)

    @pl.when(n_blocks == 0)
    def _not_decoding():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    @pl.when(n_blocks > 0)
    def _walk():
        if packed:
            # a lane group's heads as one block-diagonal operand: head
            # a's rows hold its queries in the lanes of its keys
            for h in range(hkv):
                a = h % hpg
                packed[0][h // hpg, a * rows:(a + 1) * rows,
                          a * d:(a + 1) * d] = q_ref[0, h]
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        q0 = lengths_ref[b] + t * tq               # the tile's first query
        # what runs after this tile: the row's next, or the first tile
        # of the next row that has any page
        last_tile = t + 1 == nt
        nrow = jnp.where(last_tile, next_ref[b + 1], b)
        ntile = jnp.where(last_tile, 0, t + 1)

        def product(slot, keep):
            """One fetched block against every lane group's tile of
            queries; ``keep`` None where no score of it is masked."""
            for lg in range(n_lg):
                qg = packed[0][lg] if packed else q_ref[0, lg]
                s = _mxu_dot(
                    qg, k_buf[slot, :, lg * hpg * d:(lg + 1) * hpg * d], 1, 1
                ) * sm_scale                        # [hpg * rows, kps]
                if keep is not None:
                    s = jnp.where(keep, s, _NEG_INF)
                # the running max and sum stay replicated over their
                # 128 lanes from block to block: a row's one value a
                # vreg is what a tile of this many rows cannot afford
                # (512 rows against a block of 512 keys read 4.1 us
                # that way and 2.6 this, PERF.md §6, PR 30)
                m_prev = m_ref[lg]
                m_new = jnp.maximum(
                    m_prev, jnp.max(s, axis=-1, keepdims=True)
                )
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - _lanes(m_new, kps))
                l_ref[lg] = l_ref[lg] * alpha + jnp.sum(
                    p, axis=-1, keepdims=True
                )
                v = v_buf[slot, :, lg * hpg * dv:(lg + 1) * hpg * dv]
                acc_ref[lg] = acc_ref[lg] * _lanes(
                    alpha, hpg * dv
                ) + _mxu_dot(p.astype(v.dtype), v, 1, 0)
                m_ref[lg] = m_new

        def block(i, slot):
            last = i + 1 == n_blocks

            @pl.when(jnp.logical_not(last))
            def _next_block():
                copies(b, t, i + 1, 1 - slot, wait=False)

            @pl.when(jnp.logical_and(last, nrow < n_rows))
            def _next_tile():
                copies(nrow, ntile, 0, 1 - slot, wait=False)

            copies(b, t, i, slot, wait=True)
            # key ``col`` of the block against query ``j`` of the tile:
            # seen iff col - j <= off, and inside the window iff
            # col - j > off - window
            off = q0 - i * kps
            plain = off >= kps - 1      # every key at or before query 0
            if window is not None:      # and the last query's band holds
                plain = jnp.logical_and(plain, off + tq - 1 < window)

            @pl.when(plain)
            def _unmasked():
                product(slot, None)

            @pl.when(jnp.logical_not(plain))
            def _masked():
                # rows are ordered (head of the group, query j, group
                # member): row r of a head is query r // g
                j = jax.lax.broadcasted_iota(jnp.int32, (rows, kps), 0)
                if g > 1 and (g & (g - 1)) == 0:
                    j = jnp.right_shift(j, g.bit_length() - 1)
                elif g > 1:  # Mosaic has no vector integer divide
                    j = ((j.astype(jnp.float32) + 0.5) * (1.0 / g)).astype(
                        jnp.int32
                    )
                if hpg > 1:
                    j = jnp.concatenate([j] * hpg, axis=0)
                gap = jax.lax.broadcasted_iota(jnp.int32, j.shape, 1) - j
                keep = gap <= off
                if window is not None:
                    keep = jnp.logical_and(keep, gap > off - window)
                product(slot, keep)

            return 1 - slot

        slot_ref[0] = jax.lax.fori_loop(0, n_blocks, block, slot_ref[0])
        for lg in range(n_lg):
            l = l_ref[lg]
            safe = jnp.where(l > 0, l, 1.0)
            for a in range(hpg):  # head a's own lanes of its own rows
                at = slice(a * rows, (a + 1) * rows)
                o_ref[0, lg * hpg + a] = (
                    acc_ref[lg, at, a * dv:(a + 1) * dv]
                    / _lanes(safe[at], dv)
                ).astype(o_ref.dtype)


def _paged_tiled_call(q, k_pages, v_pages, tables, lengths, keep, layer,
                      scale, window, dv):
    B, W, Hq, D = q.shape
    ps, F = k_pages.shape[-2:]
    Hkv = F // D
    G = Hq // Hkv
    n = tables.shape[1]
    pools = (k_pages, v_pages)
    k = block_pages(ps, F * k_pages.dtype.itemsize, n)
    hpg, tq, nt = query_tiles(W, G, Hkv, D)
    rows = tq * G
    pages = tile_walk(lengths.astype(jnp.int32), W, tq, ps, n, k)[0]
    if keep is not None:
        pages = jnp.where(keep[:, None], pages, 0)
    scalars, qf = _kernel_operands(
        q, Hkv, nt * rows, tables, lengths, pages, layer
    )

    def tile_spec(width):
        return pl.BlockSpec(
            (1, Hkv, rows, width), lambda b, t, *_: (b, 0, t, 0)
        )

    n_lg = Hkv // hpg
    scratch = [
        pltpu.VMEM((2, k * ps, p.shape[-1]), p.dtype) for p in pools
    ] + [
        pltpu.VMEM((n_lg, hpg * rows, hpg * D), q.dtype)
    ] * (hpg > 1) + [                                   # heads side by side
        pltpu.VMEM((n_lg, hpg * rows, hpg * dv), jnp.float32),  # acc
        pltpu.VMEM((n_lg, hpg * rows, 128), jnp.float32),  # running max
        pltpu.VMEM((n_lg, hpg * rows, 128), jnp.float32),  # running sum
    ]
    # what the kernel holds in VMEM: its scratch, the query and output
    # tiles (each double-buffered), and a lane group's scores and
    # weights in f32; as much again for what the compiler keeps besides
    # (31 MB of 63 for a Mistral chunk), and never under the
    # compiler's own default of 16 MiB
    held = sum(
        math.prod(ref.shape) * jnp.dtype(ref.dtype).itemsize
        for ref in scratch
    ) + 2 * Hkv * rows * (D + dv) * q.dtype.itemsize + (
        3 * hpg * rows * k * ps * 4
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B, nt),
        in_specs=[tile_spec(D)] + [
            pl.BlockSpec(memory_space=pl.ANY) for _ in pools
        ],
        out_specs=tile_spec(dv),
        scratch_shapes=scratch[:2] + [
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),                # the buffer in turn
        ] + scratch[2:],
    )
    out = pl.pallas_call(
        functools.partial(
            _tiled_body, sm_scale=scale, page_size=ps, k=k, hkv=Hkv,
            hpg=hpg, g=G, tq=tq, d=D, dv=dv, window=window,
            stacked=layer is not None,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, nt * rows, dv), q.dtype),
        # sequential: a tile's last block starts the next tile's first
        # fetch, and the buffer in turn passes from tile to tile
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(2 * held, 16 << 20),
        ),
        interpret=_interpret(),
        # its own name: the ticks' roofline readers sum the device time
        # of every op named for the tick's kernel
        name="paged_prefill",
    )(*scalars, qf, *pools)
    return _by_query(out, W, Hq)


def _kernel_operands(q, hkv, rows, tables, lengths, pages, layer):
    """What either body is handed ahead of the pools: the prefetched
    scalars — lengths, the pages each walk reaches (``pages``: ``[B]``,
    a row's, or ``[B, tiles]``, each tile's), ``next_row[b]`` the first
    row at or after ``b`` with a page (``B``: none), the tables, and a
    stacked pool's plane — and the queries as ``[B, Hkv, rows, D]``, a
    kv head's ``W * G`` rows (query j, group member) zero-padded."""
    B, W, Hq, D = q.shape
    first = pages.reshape(B, -1)[:, 0]
    rows_with = jnp.where(first > 0, jnp.arange(B, dtype=jnp.int32), B)
    next_row = jnp.concatenate([
        jax.lax.cummin(rows_with, reverse=True),
        jnp.full((1,), B, jnp.int32),
    ])
    qf = q.reshape(B, W, hkv, Hq // hkv, D).transpose(0, 2, 1, 3, 4)
    qf = qf.reshape(B, hkv, W * Hq // hkv, D)
    qf = jnp.pad(qf, ((0, 0), (0, 0), (0, rows - qf.shape[2]), (0, 0)))
    scalars = (
        lengths.astype(jnp.int32), pages.reshape(-1), next_row,
        tables.astype(jnp.int32),
    )
    if layer is not None:
        scalars += (jnp.asarray(layer, jnp.int32).reshape(1),)
    return scalars, qf


def _by_query(out, w, hq):
    """A body's ``[B, Hkv, rows, dv]`` back as ``[B, W, Hq, dv]``."""
    B, hkv, _, dv = out.shape
    out = out[:, :, :w * hq // hkv].reshape(B, hkv, w, hq // hkv, dv)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, w, hq, dv)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _paged_kernel_call(q, k_pages, v_pages, tables, lengths, keep, layer,
                       scale, window, dv):
    B, W, Hq, D = q.shape
    ps, F = k_pages.shape[-2:]
    Hkv = F // D
    G = Hq // Hkv
    n = tables.shape[1]
    if is_chunk(W) and v_pages is not None:
        # a prompt chunk over a K and a V pool: tiles of its queries
        return _paged_tiled_call(
            q, k_pages, v_pages, tables, lengths, keep, layer, scale,
            window, dv,
        )
    pools = (k_pages,) if v_pages is None else (k_pages, v_pages)
    k = block_pages(ps, F * k_pages.dtype.itemsize, n)
    # the grid is the rows; a row's steps are its own blocks, counted
    # in the kernel from the prefetched pages it reaches. A row that is
    # not decoding reaches none
    pages, _ = row_walk(lengths.astype(jnp.int32), W, ps, n, k)
    if keep is not None:
        pages = jnp.where(keep, pages, 0)
    # the rows of a kv head's queries, zero-padded to the sublane tile
    rows = -(-W * G // 8) * 8
    scalars, qf = _kernel_operands(
        q, Hkv, rows, tables, lengths, pages, layer
    )

    # a grid step serves a group of rows (a row alone costs a step's
    # fixed price, which a slot that is not decoding would pay too)
    group = max(r for r in range(1, 9) if B % r == 0)

    def row_spec(width):
        return pl.BlockSpec(
            (group, Hkv, rows, width), lambda b, *_: (b, 0, 0, 0)
        )

    # the pools stay where they lie: the operand is the leaf itself in
    # whatever memory it has, and the kernel copies the frames it needs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B // group,),
        in_specs=[row_spec(D)] + [
            pl.BlockSpec(memory_space=pl.ANY) for _ in pools
        ],
        out_specs=row_spec(dv),
        scratch_shapes=[
            pltpu.VMEM((2, k * ps, p.shape[-1]), p.dtype) for p in pools
        ] + [
            pltpu.SemaphoreType.DMA((len(pools), 2)),
            pltpu.SMEM((1,), jnp.int32),                # the buffer in turn
            pltpu.VMEM((Hkv * rows, F), q.dtype),       # queries, all heads
            pltpu.VMEM((Hkv * rows, Hkv * dv), jnp.float32),  # acc
            pltpu.VMEM((Hkv * rows, 128), jnp.float32),  # running max
            pltpu.VMEM((Hkv * rows, 128), jnp.float32),  # running sum
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel_body, sm_scale=scale, page_size=ps, k=k, hkv=Hkv, g=G,
            w=W, d=D, dv=dv, window=window, n_pools=len(pools),
            stacked=layer is not None,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, dv), q.dtype),
        # sequential: a row's last step starts the next row's first
        # fetch, and the buffer in turn passes from row to row
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=_interpret(),
        name="paged_attention",
    )(*scalars, qf, *pools)
    return _by_query(out, W, Hq)
