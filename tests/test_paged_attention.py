"""Paged-attention decode (ops/paged_attention + engine wiring, round 12).

Contracts under test, on top of test_serve_paged.py's parity suite:

* the op: the ``gather`` impl is BIT-IDENTICAL per dtype to
  ``gather_pages``-style dense materialization + the unchanged
  ``dot_product_attention`` (the zero-tail argument made executable);
  ``paged_attention_reference`` (lax.scan online softmax, selected by
  no impl value) and the Pallas ``kernel`` (interpret off-TPU) match
  the dense path to explicit per-dtype tolerances — online softmax
  reorders reductions, so their parity is last-ulp-class, pinned, not
  assumed; the setter and the op take ``auto|gather|kernel`` and name
  them when they refuse another;
* null-page frame 0 is unobservable (garbage in frame 0 changes no
  output), ragged lengths (including 0) and the ``[W > 1]`` verify
  block's internal causal order mask inside the op, GQA maps kv heads
  flash-style, sliding windows compose;
* per-page writes land exactly where the page table says, and dropped
  rows (keep=False) never touch the pool — the scatter_kv invariant
  carried to the new write path;
* the engine: a long-context workload and an int8 cache emit solo
  ``generate``'s streams; ``EngineConfig`` has the ten fields it has;
  slot reuse across length buckets recompiles AT MOST once per bucket
  (a second wave of the same shape compiles nothing); CoW-shared pages attend
  correctly while BOTH sharers are live mid-decode; the ``[k+1]``
  paged verify stays bit-identical to solo generate; precompiling
  buckets is bitwise state-neutral; ``auto_page_size`` warns once on
  the odd-max_len 1-token-page degeneration.
"""

import dataclasses
import logging
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.generation import generate
from pytorch_distributed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from pytorch_distributed_tpu.ops.attention import dot_product_attention
from pytorch_distributed_tpu.ops.paged_attention import (
    PagedKVQuant,
    paged_attention,
    paged_attention_reference,
    paged_write,
    set_paged_attention_impl,
)
from pytorch_distributed_tpu.serve import (
    EngineConfig,
    Request,
    RequestStatus,
    ServeEngine,
    SpecConfig,
    auto_page_size,
)
from pytorch_distributed_tpu.serve.kv_slots import (
    reset_page_size_warnings,
)

pytestmark = pytest.mark.serve

# the two impls the setter selects, and the float reference beside
# them (``paged_attention_reference``, called directly: no impl value)
IMPLS = ("gather", "reference", "kernel")
# how a pool leaf is stored: per layer ([P1, ps, Hkv * D], an unrolled
# stack), or stacked [L, P1, ps, Hkv * D] with the op reading ONE plane
# of it in place (a scanned stack; the other planes hold noise, so a
# wrong plane or a write beside the plane cannot pass)
STACKS = (None, 1, 3)


def _pool_case(rng, *, B=4, W=1, Hq=4, Hkv=2, D=16, ps=8, n=4,
               dtype=jnp.float32, max_length=None):
    """A random pool + tables + ragged lengths; frame 0 stays zero. The
    pools are LOGICAL ``[P1, ps, Hkv, D]`` (what the dense reference
    reads); :func:`_leaf` folds them into the stored form."""
    P1 = B * n + 1
    q = jnp.asarray(rng.standard_normal((B, W, Hq, D)), dtype)
    kp = jnp.asarray(rng.standard_normal((P1, ps, Hkv, D)), dtype)
    vp = jnp.asarray(rng.standard_normal((P1, ps, Hkv, D)), dtype)
    kp = kp.at[0].set(0.0)
    vp = vp.at[0].set(0.0)
    tables = jnp.asarray(
        np.arange(1, B * n + 1).reshape(B, n), jnp.int32
    )
    hi = max_length if max_length is not None else n * ps - W
    lengths = jnp.asarray(
        rng.integers(0, hi + 1, size=B), jnp.int32
    )
    return q, kp, vp, tables, lengths


def _leaf(pool, L, fold=2):
    """A logical pool as the leaf the engine stores, and its layer:
    the last ``fold`` dims folded lane-dense; with ``L`` it is plane
    ``L // 2`` of a stacked leaf whose other planes are noise."""
    flat = pool.reshape(pool.shape[:-fold] + (-1,))
    if L is None:
        return flat, None
    layer = L // 2
    noise = np.random.default_rng(99).integers(-100, 100, (L,) + flat.shape)
    leaf = jnp.asarray(noise, flat.dtype).at[layer].set(flat)
    return leaf, jnp.asarray(layer, jnp.int32)


def _paged(q, kp, vp, L, impl=None, **kw):
    """``paged_attention`` (or the reference) over the stored form of
    logical pools."""
    kl, layer = _leaf(kp, L)
    vl, _ = _leaf(vp, L)
    if impl == "reference":
        return paged_attention_reference(q, kl, vl, layer=layer, **kw)
    return paged_attention(q, kl, vl, layer=layer, impl=impl, **kw)


def _dense_ref(q, kp, vp, tables, lengths, **kw):
    """The pre-paged path: materialize the tables densely, run the
    unchanged dot_product_attention with per-row offsets."""
    B, n = tables.shape
    ps = kp.shape[1]
    kd = jnp.take(kp, tables.reshape(-1), axis=0).reshape(
        B, n * ps, kp.shape[2], kp.shape[3]
    )
    vd = jnp.take(vp, tables.reshape(-1), axis=0).reshape(
        B, n * ps, vp.shape[2], vp.shape[3]
    )
    return dot_product_attention(
        q, kd, vd, causal=True, q_offset=lengths, **kw
    )


@pytest.mark.parametrize("L", STACKS)
class TestPagedAttentionOp:
    def test_gather_impl_bit_exact_per_dtype(self, L):
        """The engine-default CPU impl: bitwise the dense path, both
        dtypes — this is what keeps solo-generate parity pinned."""
        for dtype in (jnp.float32, jnp.bfloat16):
            rng = np.random.default_rng(0)
            q, kp, vp, tables, lengths = _pool_case(
                rng, W=3, dtype=dtype
            )
            ref = _dense_ref(q, kp, vp, tables, lengths)
            out = _paged(
                q, kp, vp, L, page_tables=tables, lengths=lengths,
                impl="gather",
            )
            assert out.dtype == ref.dtype
            assert np.array_equal(
                np.asarray(out, np.float32), np.asarray(ref, np.float32)
            ), str(dtype)

    @pytest.mark.parametrize("impl", ["reference", "kernel"])
    def test_streaming_impls_match_dense_per_dtype(self, impl, L):
        """Online softmax reassociates the reductions: parity with the
        dense path is pinned per dtype at explicit tolerances (f32
        last-ulp-class; bf16 dominated by its 8-bit mantissa)."""
        for dtype, tol in ((jnp.float32, 3e-6), (jnp.bfloat16, 3e-2)):
            rng = np.random.default_rng(1)
            q, kp, vp, tables, lengths = _pool_case(
                rng, W=2, dtype=dtype
            )
            ref = np.asarray(
                _dense_ref(q, kp, vp, tables, lengths), np.float32
            )
            out = np.asarray(_paged(
                q, kp, vp, L, page_tables=tables, lengths=lengths,
                impl=impl,
            ), np.float32)
            assert np.max(np.abs(out - ref)) <= tol, str(dtype)

    @pytest.mark.parametrize("impl", IMPLS)
    def test_null_page_contents_unobservable(self, impl, L):
        """Unused table entries hold frame 0; poisoning frame 0 with
        huge finite garbage must change nothing the mask admits."""
        rng = np.random.default_rng(2)
        q, kp, vp, tables, lengths = _pool_case(rng, max_length=10)
        # tail table entries -> null page (lengths <= 10 < 2 pages)
        tables = tables.at[:, 2:].set(0)
        clean = _paged(
            q, kp, vp, L, page_tables=tables, lengths=lengths, impl=impl
        )
        dirty = _paged(
            q, kp.at[0].set(1e6), vp.at[0].set(-1e6), L,
            page_tables=tables, lengths=lengths, impl=impl,
        )
        assert np.array_equal(np.asarray(clean), np.asarray(dirty))

    @pytest.mark.parametrize("impl", IMPLS)
    def test_verify_block_causal_order_and_zero_length(self, impl, L):
        """W = k+1 queries: query j sees exactly positions <= len+j
        (the fused-verify contract), including rows of length 0."""
        rng = np.random.default_rng(3)
        q, kp, vp, tables, _ = _pool_case(rng, W=4, Hq=2, Hkv=1)
        lengths = jnp.asarray([0, 3, 8, 17], jnp.int32)
        ref = np.asarray(
            _dense_ref(q, kp, vp, tables, lengths), np.float32
        )
        out = np.asarray(_paged(
            q, kp, vp, L, page_tables=tables, lengths=lengths, impl=impl
        ), np.float32)
        tol = 0.0 if impl == "gather" else 3e-6
        assert np.max(np.abs(out - ref)) <= tol

    @pytest.mark.parametrize("impl", IMPLS)
    def test_gqa_and_window(self, impl, L):
        rng = np.random.default_rng(4)
        q, kp, vp, tables, lengths = _pool_case(rng, Hq=8, Hkv=2)
        ref = np.asarray(_dense_ref(
            q, kp, vp, tables, lengths, window=5
        ), np.float32)
        out = np.asarray(_paged(
            q, kp, vp, L, page_tables=tables, lengths=lengths, window=5,
            impl=impl,
        ), np.float32)
        tol = 0.0 if impl == "gather" else 3e-6
        assert np.max(np.abs(out - ref)) <= tol

    def test_paged_write_placement_and_drop(self, L):
        rng = np.random.default_rng(6)
        ps, P1 = 4, 9
        pool, layer = _leaf(jnp.zeros((P1, ps, 2, 3), jnp.float32), L)
        tables = jnp.asarray(
            np.arange(1, 9).reshape(4, 2), jnp.int32
        )
        new = jnp.asarray(rng.standard_normal((4, 2, 2, 3)), jnp.float32)
        flat = np.asarray(new).reshape(4, 2, 6)
        wp = jnp.asarray([0, 3, 30, 6], jnp.int32)
        keep = jnp.asarray([True, True, False, True])
        whole = np.asarray(paged_write(pool, new, tables, wp, keep, layer))
        assert whole.shape == pool.shape
        out = whole if L is None else whole[int(layer)]
        # row 0: positions 0,1 -> frame tables[0,0] slots 0,1
        assert np.array_equal(out[1, 0], flat[0, 0])
        assert np.array_equal(out[1, 1], flat[0, 1])
        # row 1: positions 3,4 straddle the page boundary
        assert np.array_equal(out[3, 3], flat[1, 0])
        assert np.array_equal(out[4, 0], flat[1, 1])
        # row 2 dropped entirely even though its position (30) clamps
        # past its 2-page table — the mid-prefill-row contract (rows
        # beyond the bucket are always keep=False); row 3 lands in its
        # second page; null frame 0 never written
        written = {(1, 0), (1, 1), (3, 3), (4, 0), (8, 2), (8, 3)}
        for f in range(P1):
            for s in range(ps):
                if (f, s) not in written:
                    assert np.abs(out[f, s]).sum() == 0.0, (f, s)
        if L is not None:  # the other layers' planes: not a byte moved
            rest = np.arange(L) != int(layer)
            assert np.array_equal(whole[rest], np.asarray(pool)[rest])

    def test_validation(self, L):
        rng = np.random.default_rng(7)
        q, kp, vp, tables, lengths = _pool_case(rng)
        with pytest.raises(ValueError, match="kv heads"):
            _paged(
                q[:, :, :3], kp, vp, L, page_tables=tables,
                lengths=lengths,
            )
        with pytest.raises(ValueError, match="page_tables"):
            _paged(
                q, kp, vp, L, page_tables=tables[:2], lengths=lengths
            )
        with pytest.raises(ValueError, match="window"):
            _paged(
                q, kp, vp, L, page_tables=tables, lengths=lengths,
                window=0,
            )
        # a stacked leaf without its layer, a per-layer leaf with one
        kl, layer = _leaf(kp, L)
        wrong = jnp.asarray(0, jnp.int32) if L is None else None
        with pytest.raises(ValueError, match="with its layer"):
            paged_attention(
                q, kl, kl, page_tables=tables, lengths=lengths,
                layer=wrong,
            )
        with pytest.raises(ValueError, match="impl"):
            set_paged_attention_impl("mosaic")


# the two values that select something and ``auto`` are all there is
_NAMED = "'auto', 'gather', 'kernel'"


def test_stream_is_no_value_of_the_setter():
    with pytest.raises(ValueError, match=_NAMED):
        set_paged_attention_impl("stream")


def test_the_op_refuses_an_impl_it_does_not_know():
    """It names the values it takes; it does not fall through to the
    kernel."""
    q, kp, vp, tables, lengths = _pool_case(np.random.default_rng(9))
    with pytest.raises(ValueError, match=_NAMED):
        _paged(
            q, kp, vp, None, page_tables=tables, lengths=lengths,
            impl="stream",
        )


# the heads of the two serving cells: GPT-2-medium's 16/16 of 64 and
# Mistral's GQA at 128 (its 32/8 cut to 8/2: the group of 4 is kept)
@pytest.mark.parametrize("window", [None, 6])
@pytest.mark.parametrize("W", [1, 5])
@pytest.mark.parametrize("Hq,Hkv,D", [(16, 16, 64), (8, 2, 128)])
@pytest.mark.parametrize("L", [1, 3])
def test_kernel_reads_its_plane_in_place(L, Hq, Hkv, D, W, window):
    """The kernel (interpreted) against the exact ``gather`` impl over a
    stacked leaf: the plane comes from the prefetched layer, the frame
    from the prefetched table, and the leaf is the operand as it is."""
    rng = np.random.default_rng(8)
    q, kp, vp, tables, lengths = _pool_case(
        rng, B=2, W=W, Hq=Hq, Hkv=Hkv, D=D, n=3
    )
    kw = dict(page_tables=tables, lengths=lengths, window=window)
    ref = np.asarray(_paged(q, kp, vp, L, impl="gather", **kw))
    out = np.asarray(_paged(q, kp, vp, L, impl="kernel", **kw))
    assert np.max(np.abs(out - ref)) <= 3e-6


@pytest.mark.parametrize("L", STACKS)
@pytest.mark.parametrize("impl", ["gather", "kernel"])
def test_int8_scale_pools(impl, L):
    """Quantized pools ride as payload+scale pairs; the dequant is
    decode_cache's exact formula, so the gather impl is bitwise the
    dense int8 path. The kernel takes fp pools only and REFUSES a
    quantized one by name — never a quiet reroute to gather."""
    rng = np.random.default_rng(5)
    q, kp, vp, tables, lengths = _pool_case(rng)
    k8 = jnp.asarray(
        rng.integers(-127, 128, size=kp.shape), jnp.int8
    )
    v8 = jnp.asarray(
        rng.integers(-127, 128, size=vp.shape), jnp.int8
    )
    ks = jnp.asarray(
        rng.uniform(0.01, 0.1, size=kp.shape[:3] + (1,)),
        jnp.float32,
    )
    vs = jnp.asarray(
        rng.uniform(0.01, 0.1, size=vp.shape[:3] + (1,)),
        jnp.float32,
    )
    kd = (k8.astype(jnp.float32) * ks).astype(jnp.float32)
    vd = (v8.astype(jnp.float32) * vs).astype(jnp.float32)
    ref = np.asarray(
        _dense_ref(q, kd, vd, tables, lengths), np.float32
    )
    (k8l, layer), (v8l, _) = _leaf(k8, L), _leaf(v8, L)
    pools = (
        PagedKVQuant(k8l, _leaf(ks, L)[0], jnp.float32),
        PagedKVQuant(v8l, _leaf(vs, L)[0], jnp.float32),
    )
    if impl == "kernel":
        with pytest.raises(ValueError, match="int8 KV cache"):
            paged_attention(
                q, *pools, page_tables=tables, lengths=lengths,
                layer=layer, impl=impl,
            )
        return
    out = np.asarray(paged_attention(
        q, *pools, page_tables=tables, lengths=lengths, layer=layer,
        impl=impl,
    ), np.float32)
    assert np.array_equal(out, ref)


# -- the kernel's walk: a row's live pages, a block of them a step ---------

_PAGED = sys.modules["pytorch_distributed_tpu.ops.paged_attention"]


@pytest.fixture
def blocks_of_two(monkeypatch):
    """Blocks of 2 pages of 8: rows of a few dozen tokens then walk
    several blocks, which the shipped 512-token block would swallow."""
    monkeypatch.setattr(_PAGED, "_BLOCK_MAX_TOKENS", 16)


# lengths against blocks of 16 tokens: an empty row, one inside its
# first block, one whose queries end exactly on a block's edge (W 1 and
# W 5), one a token past the edge, and one 20x the others
RAGGED = (0, 5, 15, 11, 16, 167)


def _ragged_case(rng, *, W, n=24, lengths=RAGGED, **kw):
    """Rows of the given lengths over a table wider than any of them;
    entries past a row's last page hold the null page."""
    q, kp, vp, tables, _ = _pool_case(rng, B=len(lengths), W=W, n=n, **kw)
    ps = kp.shape[1]
    own = np.asarray([-(-(x + W) // ps) for x in lengths])
    tables = jnp.where(np.arange(n)[None, :] < own[:, None], tables, 0)
    return q, kp, vp, tables, jnp.asarray(lengths, jnp.int32), own


@pytest.mark.parametrize("window", [None, 12])
@pytest.mark.parametrize("W", [1, 5])
@pytest.mark.parametrize("L", STACKS)
def test_kernel_walks_each_rows_own_blocks(blocks_of_two, L, W, window):
    """Ragged rows, each walked for as many blocks as it has: the
    kernel against the exact ``gather`` impl."""
    rng = np.random.default_rng(20)
    q, kp, vp, tables, lengths, _ = _ragged_case(rng, W=W)
    assert _PAGED.block_pages(8, kp.shape[2] * kp.shape[3] * 4, 24) == 2
    kw = dict(page_tables=tables, lengths=lengths, window=window)
    ref = np.asarray(_paged(q, kp, vp, L, impl="gather", **kw))
    out = np.asarray(_paged(q, kp, vp, L, impl="kernel", **kw))
    assert np.max(np.abs(out - ref)) <= 3e-6


@pytest.mark.parametrize("W", [1, 5])
@pytest.mark.parametrize("L", STACKS)
def test_kernel_never_uses_a_dead_page(blocks_of_two, L, W):
    """The null page, every page past a row's length and every page of
    a row that is not decoding hold NaN: the outputs are finite and
    the clean pool's, and a row that is not decoding reads zeros."""
    rng = np.random.default_rng(21)
    q, kp, vp, tables, lengths, own = _ragged_case(rng, W=W)
    keep = np.asarray([True, True, False, True, True, True])
    n = tables.shape[1]
    # each row's pages are its own span of the pool (``_pool_case``)
    live = np.zeros(kp.shape[0], bool)
    for b in np.flatnonzero(keep):
        live[1 + b * n:1 + b * n + own[b]] = True
    dead = jnp.asarray(~live)[:, None, None, None]
    kw = dict(page_tables=tables, lengths=lengths)
    ref = np.asarray(_paged(q, kp, vp, L, impl="gather", **kw))
    out = np.asarray(_paged(
        q, jnp.where(dead, jnp.nan, kp), jnp.where(dead, jnp.nan, vp), L,
        impl="kernel", keep=jnp.asarray(keep), **kw,
    ))
    assert np.isfinite(out).all()
    assert np.max(np.abs(out[keep] - ref[keep])) <= 3e-6
    assert not out[~keep].any()


@pytest.mark.parametrize("W", [1, 5])
@pytest.mark.parametrize("L", STACKS)
def test_kernel_blocks_on_latent_pages(blocks_of_two, L, W):
    """The latent case over several blocks: 8 query heads against ONE
    640-lane frame a token, the values its first 512 lanes."""
    rng = np.random.default_rng(22)
    lengths, n, ps, H, F, r = (0, 15, 16, 70), 10, 8, 8, 640, 512
    B = len(lengths)
    q = jnp.asarray(rng.standard_normal((B, W, H, F)), jnp.float32)
    pool = jnp.asarray(rng.standard_normal((B * n + 1, ps, F)), jnp.float32)
    leaf, layer = _leaf(pool, L, fold=1)
    tables = jnp.asarray(np.arange(1, B * n + 1).reshape(B, n), jnp.int32)
    kw = dict(
        page_tables=tables, lengths=jnp.asarray(lengths, jnp.int32),
        layer=layer, scale=F ** -0.5,
    )
    ref, out = (
        np.asarray(paged_attention(
            q, leaf, _PAGED.PagedPrefix(leaf, r), impl=impl, **kw
        ))
        for impl in ("gather", "kernel")
    )
    assert out.shape == (B, W, H, r)
    assert np.max(np.abs(out - ref)) <= 3e-6


@pytest.mark.parametrize("page_size,token_bytes,n_pages,want", [
    (16, 2048, 256, 32),    # Mistral and GPT-2: 512 tokens a block
    (16, 1280, 512, 32),    # latent frames: the token cap, not the bytes
    (16, 2048, 8, 8),       # never wider than the table
    (16, 2048, 24, 16),     # a power of two
    (16, 8192, 256, 8),     # f32 frames four times as wide: the budget
    (1024, 2048, 4, 1),     # a page above the cap is still one page
])
def test_block_pages_follow_the_frame_and_the_table(
    page_size, token_bytes, n_pages, want
):
    assert _PAGED.block_pages(page_size, token_bytes, n_pages) == want


@pytest.mark.parametrize("w", [1, 5])
def test_row_walk_counts_pages_and_blocks(w):
    """By hand at pages of 16 in blocks of 4: a row of L tokens and w
    queries reaches ceil((L + w) / 16) pages, never past the table."""
    lengths = np.asarray([0, 15, 16, 63, 64, 5000])
    pages, blocks = _PAGED.row_walk(lengths, w, 16, 64, 4)
    by_hand = {1: ([1, 1, 2, 4, 5, 64], [1, 1, 1, 1, 2, 16]),
               5: ([1, 2, 2, 5, 5, 64], [1, 1, 1, 2, 2, 16])}[w]
    assert pages.tolist() == by_hand[0] and blocks.tolist() == by_hand[1]


# -- many queries a row: the query-tiled body (a prompt chunk) --------------


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 32 query rows over blocks of 2 pages of 8, and every
    kernel call over a K and a V pool through the tiled body: a chunk
    of a few dozen queries then spans several tiles and several
    blocks."""
    monkeypatch.setattr(_PAGED, "_BLOCK_MAX_TOKENS", 16)
    monkeypatch.setattr(_PAGED, "_TILE_ROWS", 32)
    monkeypatch.setattr(_PAGED, "_CHUNK_QUERIES", 1)


# (query heads, kv heads, head size, value lanes of a latent frame) and
# the W that is exactly one tile of 32 rows there. A latent frame's many
# queries keep the tick's body (no model sends it a chunk: MLAttention
# decodes the row's frames), so its cases hold that body to the same
TILED_GEOMETRIES = {
    "gqa4x1x128": (4, 1, 128, None, 8),
    "mha4x64": (4, 4, 64, None, 16),    # two heads a 128-lane group
    "latent8x640": (8, 1, 640, 512, 4),
}


def _chunk_case(rng, geometry, W):
    """Three rows of one chunk each: an empty row, one mid-page, and
    one right behind three whole pages it SHARES with the second (a
    prefix hit). The table is twice as wide as any row reaches; what
    no row owns is the null page, and the null page, like every frame
    no row owns, holds NaN in ``dirty``."""
    Hq, Hkv, D, r, _ = TILED_GEOMETRIES[geometry]
    ps, n = 8, 16
    lengths = np.asarray([0, 29, 24])
    own = -(-(lengths + W) // ps)
    assert own.max() * 2 <= n
    tables = np.zeros((3, n), np.int32)
    at = 1
    for b in range(3):
        tables[b, :own[b]] = np.arange(at, at + own[b])
        at += own[b]
    tables[2, :3] = tables[1, :3]
    P1 = at + 3
    q = jnp.asarray(rng.standard_normal((3, W, Hq, D)), jnp.float32)
    pools = [
        jnp.asarray(rng.standard_normal((P1, ps, Hkv * D)), jnp.float32)
        for _ in range(1 if r else 2)
    ]
    live = np.zeros(P1, bool)
    live[tables[tables > 0]] = True
    dead = jnp.asarray(~live)[:, None, None]
    dirty = [jnp.where(dead, jnp.nan, p) for p in pools]
    clean = [jnp.where(dead, 0.0, p) for p in pools]
    return q, clean, dirty, jnp.asarray(tables), jnp.asarray(
        lengths, jnp.int32)


@pytest.mark.parametrize("L", [None, 3], ids=["leaf", "stacked"])
@pytest.mark.parametrize("window", [None, 3])
@pytest.mark.parametrize("tiles", ["under", "at", "over"])
@pytest.mark.parametrize("geometry", list(TILED_GEOMETRIES))
def test_tiled_body_is_the_chunks_attention(
    small_tiles, geometry, tiles, window, L
):
    """The tiled body (a latent frame: the tick's, many queries a row)
    against the exact ``gather`` impl and the float reference, per
    geometry: W under, at and over one query tile (over: two tiles and
    a part of a third), with and without a window that binds inside
    the chunk, a leaf alone and a plane of a stacked one. It runs over
    pools whose dead frames hold NaN."""
    Hq, Hkv, D, r, at = TILED_GEOMETRIES[geometry]
    W = {"under": at - 1 if at > 4 else 3, "at": at, "over": 2 * at + 3}[
        tiles]
    rng = np.random.default_rng(30)
    q, clean, dirty, tables, lengths = _chunk_case(rng, geometry, W)
    hpg, tq, nt = _PAGED.query_tiles(W, Hq // Hkv, Hkv, D)
    assert hpg * tq * (Hq // Hkv) == 32 or tiles == "under"
    assert nt == {"under": 1, "at": 1, "over": 3}[tiles]
    kw = dict(page_tables=tables, lengths=lengths, window=window,
              scale=D ** -0.5)

    def run(pools, impl):
        leaves, layer = zip(*[_leaf(p, L, fold=1) for p in pools])
        if impl == "reference":
            return paged_attention_reference(
                q, leaves[0], None if r else leaves[1], layer=layer[0],
                value_dim=r, **kw)
        v = _PAGED.PagedPrefix(leaves[0], r) if r else leaves[1]
        return paged_attention(
            q, leaves[0], v, layer=layer[0], impl=impl, **kw)

    out = np.asarray(run(dirty, "kernel"))
    assert out.shape == (3, W, Hq, r or D) and np.isfinite(out).all()
    for impl in ("gather", "reference"):
        ref = np.asarray(run(clean, impl))
        assert np.max(np.abs(out - ref)) <= 3e-6, impl


@pytest.mark.parametrize("W,G,Hkv,D,body", [
    (1, 4, 8, 128, "paged_attention"),      # Mistral's tick
    (5, 4, 8, 128, "paged_attention"),      # and its verify, k = 4
    (1, 1, 16, 64, "paged_attention"),      # GPT-2's
    (5, 1, 16, 64, "paged_attention"),
    (15, 1, 16, 64, "paged_attention"),     # the longest verify
    (1, 64, 1, 640, "paged_attention"),     # the latent tick: 64 rows
    (5, 64, 1, 640, "paged_attention"),     # and its verify: 320
    (64, 64, 1, 640, "paged_attention"),    # one pool: the one body
    (512, 4, 8, 128, "paged_prefill"),      # the chunks: Mistral's,
    (128, 1, 16, 64, "paged_prefill"),      # GPT-2's in the cell,
    (64, 1, 16, 64, "paged_prefill"),       # in chip_smoke.py,
    (16, 1, 16, 64, "paged_prefill"),       # and the shortest there is
])
def test_the_body_is_read_off_the_calls_shapes(W, G, Hkv, D, body):
    """A tick and a speculative verify keep the one block-diagonal
    operand, whatever rows a kv head brings; a call of 16 queries a row
    or more over a K and a V pool takes the tiled body, under a name of
    its own (the ticks' roofline readers sum every op named
    ``paged_attention``)."""
    sds = jax.ShapeDtypeStruct
    pool = sds((9, 16, Hkv * D), jnp.float32)
    text = str(jax.make_jaxpr(lambda q, k, t, l: paged_attention(
        q, k, _PAGED.PagedPrefix(k, 512) if Hkv * D == 640 else k,
        page_tables=t, lengths=l, impl="kernel",
    ))(sds((2, W, G * Hkv, D), jnp.float32), pool,
       sds((2, 4), jnp.int32), sds((2,), jnp.int32)))
    assert f"name={body}\n" in text or f"name={body} " in text, text[-600:]
    assert ("paged_attention" in body) != ("name=paged_prefill" in text)


@pytest.mark.parametrize("w,g,hkv,d,want", [
    (512, 4, 8, 128, (1, 256, 2)),     # Mistral: 1024 rows a head a tile
    (128, 1, 16, 64, (2, 128, 1)),     # GPT-2: two heads a lane group
    (64, 1, 16, 64, (2, 64, 1)),       # a chunk shorter than a tile
    (127, 1, 16, 64, (2, 128, 1)),     # whole sublane tiles of positions
    (100, 3, 2, 16, (2, 112, 1)),      # whole sublane tiles of rows
])
def test_query_tiles_follow_the_heads_and_the_chunk(w, g, hkv, d, want):
    assert _PAGED.query_tiles(w, g, hkv, d) == want


def test_tile_walk_counts_a_rows_prefix_once_a_tile():
    """By hand at pages of 16 in blocks of 4: 512 queries behind 2048
    cached tokens in tiles of 256 reach 144 and 160 pages, 36 and 40
    blocks; one tile of them all is ``row_walk``."""
    lengths = np.asarray([2048, 0])
    pages, blocks = _PAGED.tile_walk(lengths, 512, 256, 16, 256, 4)
    assert pages.tolist() == [[144, 160], [16, 32]]
    assert blocks.tolist() == [[36, 40], [4, 8]]
    one = _PAGED.tile_walk(lengths, 512, 512, 16, 256, 4)
    row = _PAGED.row_walk(lengths, 512, 16, 256, 4)
    assert one[0][:, 0].tolist() == row[0].tolist() == [160, 32]
    assert one[1][:, 0].tolist() == row[1].tolist()


# -- engine wiring ----------------------------------------------------------


@pytest.fixture(scope="module")
def long_ctx():
    """A tiny model whose position table allows a LONG max_len with
    short live lengths — the regime paged attention exists for."""
    cfg = GPT2Config(
        vocab_size=97, n_positions=256, hidden_size=32, num_layers=2,
        num_heads=2, dropout_rate=0.0,
    )
    model = GPT2LMHead(cfg)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def _solo(model, params, req: Request):
    out = np.asarray(generate(
        model, params, jnp.asarray(req.prompt_ids[None]),
        max_new_tokens=req.max_new_tokens,
        temperature=req.temperature, top_k=req.top_k, top_p=req.top_p,
        rng=jax.random.PRNGKey(req.seed), eos_id=req.eos_id,
    ))[0, req.prompt_len:]
    return [int(x) for x in out]


def _workload(rng, n, p_rng=(3, 9), n_rng=(4, 12)):
    return [
        Request(
            rng.integers(1, 97, size=int(
                rng.integers(p_rng[0], p_rng[1] + 1)
            )).astype(np.int32),
            max_new_tokens=int(rng.integers(n_rng[0], n_rng[1] + 1)),
            temperature=(0.0 if i % 2 else 0.8),
            top_k=(None if i % 2 else 7), seed=i,
        )
        for i in range(n)
    ]


def _serve_like_solo(engine, model, params, reqs):
    """Every request completes with solo ``generate``'s stream."""
    hs = [engine.submit(r) for r in reqs]
    engine.run_until_drained()
    for r, h in zip(reqs, hs):
        assert h.status is RequestStatus.COMPLETED
        assert h.tokens == _solo(model, params, r)


class TestPagedEngine:
    def test_long_context_workload_matches_solo_generate(self, long_ctx):
        """A seeded greedy-and-sampled workload at a ``max_len`` far
        past the live lengths: every stream is solo ``generate``'s, and
        each occupied bucket (2-4 of 16 pages) compiled once."""
        model, params = long_ctx
        rng = np.random.default_rng(11)
        engine = ServeEngine(model, params, EngineConfig(
            num_slots=4, max_len=128, prefill_chunk=4, page_size=8,
        ))
        _serve_like_solo(engine, model, params, _workload(rng, 8))
        assert engine.decode_buckets < set(engine._buckets)
        assert max(engine.decode_buckets) < engine.pool.max_pages
        assert engine.decode_compiles == len(engine.decode_buckets)
        assert all(
            v == 1 for v in engine._decode_bucket_compiles.values()
        )

    def test_int8_kv_cache_matches_solo_generate(self, monkeypatch):
        """kv_cache_quantize='int8' rides the paged path as payload +
        scale pools (PagedKVQuant): the per-page dequant is
        decode_cache's exact formula, so the engine emits the streams
        solo ``generate`` emits on the same int8 cache."""
        cfg = GPT2Config(
            vocab_size=97, n_positions=96, hidden_size=32,
            num_layers=2, num_heads=2, dropout_rate=0.0,
            kv_cache_quantize="int8",
        )
        model = GPT2LMHead(cfg)
        params = model.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        rng = np.random.default_rng(17)
        engine = ServeEngine(model, params, EngineConfig(
            num_slots=2, max_len=64, prefill_chunk=4, page_size=8,
        ))
        _serve_like_solo(engine, model, params, _workload(rng, 4))
        # where the kernel is what "auto" means (a TPU), the same engine
        # is refused at construction, by name — the flag is patched
        # directly: the setter would drop every jit cache in the process
        monkeypatch.setattr(_PAGED, "_IMPL", "kernel")
        with pytest.raises(ValueError, match="int8 KV cache"):
            ServeEngine(model, params, EngineConfig(
                num_slots=2, max_len=64, prefill_chunk=4, page_size=8,
            ))

    def test_engine_config_fields_are_the_ten(self):
        """A knob taken out (a second decode path, a sleep in the loop)
        cannot come back unnoticed."""
        assert [f.name for f in dataclasses.fields(EngineConfig)] == [
            "num_slots", "max_len", "prefill_chunk",
            "prefill_chunks_per_step", "telemetry_every", "page_size",
            "num_pages", "prefix_cache", "role", "engine_id",
        ]

    def test_slot_reuse_recompiles_at_most_once_per_bucket(
        self, long_ctx
    ):
        """Lengths crossing page-bucket boundaries compile each bucket
        once; a second wave re-occupying the same buckets (slot reuse)
        compiles NOTHING new."""
        model, params = long_ctx
        engine = ServeEngine(model, params, EngineConfig(
            num_slots=2, max_len=128, prefill_chunk=4, page_size=4,
        ))
        rng = np.random.default_rng(12)

        def wave():
            reqs = [
                Request(
                    rng.integers(1, 97, size=5).astype(np.int32),
                    max_new_tokens=20,
                ),
                Request(
                    rng.integers(1, 97, size=9).astype(np.int32),
                    max_new_tokens=30,
                ),
            ]
            hs = [engine.submit(r) for r in reqs]
            engine.run_until_drained()
            assert all(
                h.status is RequestStatus.COMPLETED for h in hs
            )
            for r, h in zip(reqs, hs):
                assert h.tokens == _solo(model, params, r)

        wave()
        # lengths reached ~39 -> buckets {2, 4, 8, 16} of 32 possible
        assert len(engine.decode_buckets) >= 2
        assert engine.decode_compiles == len(engine.decode_buckets)
        compiles = (engine.decode_compiles, engine.prefill_compiles)
        wave()  # slot reuse over the same length profile
        assert (
            engine.decode_compiles, engine.prefill_compiles
        ) == compiles, "slot reuse recompiled an already-built bucket"
        assert all(
            v == 1 for v in engine._decode_bucket_compiles.values()
        )
        assert all(
            v == 1 for v in engine._prefill_bucket_compiles.values()
        )

    @pytest.mark.parametrize("body", ["gpt2-L2", "gpt2-L1", "llama-gqa-L3"])
    def test_cow_shared_pages_attend_correctly_mid_share(
        self, long_ctx, body
    ):
        """Two live requests decode over the SAME refcounted prompt
        pages simultaneously — the paged stream reads shared (read-only)
        frames for both rows, streams stay solo-exact, and the shared
        frames' bytes never change while both attend them: not through
        a neighbour's chunk, not through its ticks, at any depth."""
        from tests.test_serve_paged import _page_bytes, make_body

        model, params = long_ctx
        if body != "gpt2-L2":
            model, params = make_body(body, n_positions=256)
        rng = np.random.default_rng(13)
        sys_p = rng.integers(1, 97, size=16).astype(np.int32)

        def mk(new, **kw):
            return Request(
                np.concatenate([
                    sys_p, rng.integers(1, 97, size=3).astype(np.int32)
                ]),
                max_new_tokens=new, **kw,
            )

        engine = ServeEngine(model, params, EngineConfig(
            num_slots=3, max_len=64, prefill_chunk=4, page_size=4,
        ))
        seed_req = mk(2)
        hs = engine.submit(seed_req)
        engine.run_until_drained()  # registers the 4-page system prefix
        assert hs.status is RequestStatus.COMPLETED
        r1, r2 = mk(12), mk(10, temperature=0.7, top_p=0.9, seed=5)
        h1, h2 = engine.submit(r1), engine.submit(r2)
        for _ in range(3):
            engine.step()
        # both rows live and decoding over the shared frames
        assert h1.status is RequestStatus.DECODING
        assert h2.status is RequestStatus.DECODING
        shared = list(
            engine.scheduler.by_slot[h1.slot]._lease.page_row[:4]
        )
        assert shared == list(
            engine.scheduler.by_slot[h2.slot]._lease.page_row[:4]
        )
        before = _page_bytes(engine.pool, shared)
        engine.run_until_drained()
        assert h1.tokens == _solo(model, params, r1)
        assert h2.tokens == _solo(model, params, r2)
        assert _page_bytes(engine.pool, shared) == before
        engine.pool.check_consistency()

    def test_spec_paged_verify_long_context_parity(self, long_ctx):
        """The [k+1] verify rides the paged primitive: greedy spec
        streams stay bit-identical to solo generate at a long max_len
        with multiple buckets occupied."""
        model, params = long_ctx
        dcfg = GPT2Config(
            vocab_size=97, n_positions=256, hidden_size=16,
            num_layers=1, num_heads=2, dropout_rate=0.0,
        )
        dmodel = GPT2LMHead(dcfg)
        dparams = dmodel.init(
            jax.random.key(1), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        engine = ServeEngine(
            model, params,
            EngineConfig(num_slots=2, max_len=128, prefill_chunk=4,
                         page_size=4),
            spec=SpecConfig(dmodel, dparams, num_draft_tokens=3),
        )
        rng = np.random.default_rng(14)
        reqs = [
            Request(rng.integers(1, 97, size=6).astype(np.int32),
                    max_new_tokens=24),
            Request(rng.integers(1, 97, size=10).astype(np.int32),
                    max_new_tokens=18),
        ]
        hs = [engine.submit(r) for r in reqs]
        engine.run_until_drained()
        for r, h in zip(reqs, hs):
            assert h.status is RequestStatus.COMPLETED
            assert h.tokens == _solo(model, params, r)
        assert engine.spec_verifies > 0
        assert len(engine.decode_buckets) >= 2
        assert engine.decode_compiles == len(engine.decode_buckets)
        engine.pool.check_consistency()
        engine.draft_pool.check_consistency()

    def test_precompile_buckets_is_state_neutral(self, long_ctx):
        """precompile_decode_buckets compiles every bucket via no-op
        dispatches: device rows and the pool stay bitwise intact."""
        model, params = long_ctx
        engine = ServeEngine(model, params, EngineConfig(
            num_slots=2, max_len=64, prefill_chunk=4, page_size=8,
        ))
        rng = np.random.default_rng(15)
        r = Request(rng.integers(1, 97, size=5).astype(np.int32),
                    max_new_tokens=4)
        h = engine.submit(r)
        engine.run_until_drained()
        before = (
            np.asarray(engine._toks).copy(),
            np.asarray(engine._lengths).copy(),
            np.asarray(engine._keys).copy(),
            [np.asarray(x).copy() for x in
             jax.tree_util.tree_leaves(engine.pool.cache)
             if x.ndim >= 2],
        )
        engine.precompile_decode_buckets()
        assert engine.decode_compiles == len(engine._buckets)
        assert np.array_equal(before[0], np.asarray(engine._toks))
        assert np.array_equal(before[1], np.asarray(engine._lengths))
        assert np.array_equal(before[2], np.asarray(engine._keys))
        after = [
            np.asarray(x) for x in
            jax.tree_util.tree_leaves(engine.pool.cache) if x.ndim >= 2
        ]
        for a, b in zip(before[3], after):
            assert np.array_equal(a, b)
        # ...and a request decoded afterwards is still solo-exact
        r2 = Request(rng.integers(1, 97, size=4).astype(np.int32),
                     max_new_tokens=5)
        h2 = engine.submit(r2)
        engine.run_until_drained()
        assert h2.tokens == _solo(model, params, r2)
        assert h.status is RequestStatus.COMPLETED

    def test_auto_page_size_warns_once_on_odd_max_len(self, caplog):
        reset_page_size_warnings()
        ns = logging.getLogger("pytorch_distributed_tpu")
        ns.addHandler(caplog.handler)
        try:
            with caplog.at_level(
                logging.WARNING, logger="pytorch_distributed_tpu"
            ):
                assert auto_page_size(63) == 1
                assert auto_page_size(63) == 1  # deduped
                assert auto_page_size(64) == 32  # healthy: silent
        finally:
            ns.removeHandler(caplog.handler)
        warns = [
            r for r in caplog.records
            if "1-token pages" in r.getMessage()
        ]
        assert len(warns) == 1
        reset_page_size_warnings()
