"""Paged KV pool + engine-tick speculative decoding (serve/, round 11).

Contracts under test, on top of test_serve.py's parity suite:

* page bookkeeping — refcounts, the shared free list, strict-FIFO
  head-of-line admission under page pressure, registry eviction — stays
  consistent through every lifecycle storm (``check_consistency`` after
  each), and shared pages are bitwise READ-ONLY (the copy-on-write
  discipline, checked by checksumming the device pages);
* prefix sharing changes memory and compute, never tokens: a request
  admitted onto shared pages emits exactly its solo ``generate`` stream;
* the bounded-compile-count invariant holds with pages AND speculation:
  one prefill program and one tick program per OCCUPIED length bucket
  (round 12's static bucket widths), each compiled exactly once, for
  any workload mix;
* greedy speculative output is BIT-IDENTICAL to solo generate (the
  verify accepts exactly the target's own argmax chain), sampled rows
  are deterministic given seeds, and mid-speculation eviction /
  cancellation / fault leaves both pools refcount-consistent.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.generation import generate
from pytorch_distributed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from pytorch_distributed_tpu.runtime import faults
from pytorch_distributed_tpu.serve import (
    EngineConfig,
    PagedKVPool,
    Request,
    RequestStatus,
    ServeEngine,
    ServeTelemetry,
    SpecConfig,
    auto_page_size,
    prefix_shared_requests,
)
from pytorch_distributed_tpu.train.metrics import MetricsWriter, read_metrics

pytestmark = pytest.mark.serve


@pytest.fixture(scope="module")
def gpt2():
    cfg = GPT2Config(
        vocab_size=97, n_positions=96, hidden_size=32, num_layers=2,
        num_heads=2, dropout_rate=0.0,
    )
    model = GPT2LMHead(cfg)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


@pytest.fixture(scope="module")
def draft(gpt2):
    cfg = GPT2Config(
        vocab_size=97, n_positions=96, hidden_size=16, num_layers=1,
        num_heads=2, dropout_rate=0.0,
    )
    model = GPT2LMHead(cfg)
    params = model.init(
        jax.random.key(1), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


# the pool's passage (a stacked leaf carried through the layer
# loop, each layer writing and reading its own plane in place) pinned
# across what it can observe: depth 1 and deeper, MHA and GQA
BODIES = ("gpt2-L2", "gpt2-L1", "llama-gqa-L3")


def _init(model, seed):
    return model.init(
        jax.random.key(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]


def _llama(layers, hidden=32, n_positions=96):
    from pytorch_distributed_tpu.models.llama import (
        LlamaConfig, LlamaForCausalLM,
    )

    return LlamaForCausalLM(LlamaConfig(
        vocab_size=97, hidden_size=hidden, num_layers=layers, num_heads=4,
        num_kv_heads=2, intermediate_size=2 * hidden,
        max_seq_len=n_positions,
    ))


def make_body(name, n_positions=96):
    """(model, params) of one of :data:`BODIES` (vocab 97) — shared by
    the parity suites that pin the pool's passage per body."""
    if name == "llama-gqa-L3":
        model = _llama(3, n_positions=n_positions)
    else:
        model = GPT2LMHead(GPT2Config(
            vocab_size=97, n_positions=n_positions, hidden_size=32,
            num_layers=int(name[-1]), num_heads=2, dropout_rate=0.0,
        ))
    return model, _init(model, 0)


@pytest.fixture(scope="module", params=BODIES)
def body(request, gpt2, draft):
    """((model, params), (draft model, draft params)) per body."""
    if request.param == "gpt2-L2":
        return gpt2, draft
    if request.param == "llama-gqa-L3":
        dmodel = _llama(1, hidden=16)
        draft = dmodel, _init(dmodel, 1)
    return make_body(request.param), draft


def _solo(model, params, req: Request):
    out = np.asarray(generate(
        model, params, jnp.asarray(req.prompt_ids[None]),
        max_new_tokens=req.max_new_tokens,
        temperature=req.temperature, top_k=req.top_k, top_p=req.top_p,
        rng=jax.random.PRNGKey(req.seed), eos_id=req.eos_id,
    ))[0, req.prompt_len:]
    toks = [int(x) for x in out]
    if req.eos_id is not None and req.eos_id in toks:
        toks = toks[: toks.index(req.eos_id) + 1]
    return toks


def _assert_bucketed_compiles(engine):
    """Round-12 bounded-compile contract: one program per OCCUPIED
    length bucket, each compiled exactly once, at most
    log2(max_pages) + 1 buckets per program kind."""
    assert engine.decode_compiles == len(engine.decode_buckets)
    assert engine.prefill_compiles == len(engine.prefill_buckets)
    cap = len(engine._buckets)
    assert 1 <= len(engine.decode_buckets) <= cap
    assert 1 <= len(engine.prefill_buckets) <= cap
    assert all(
        v == 1 for v in engine._decode_bucket_compiles.values()
    )
    assert all(
        v == 1 for v in engine._prefill_bucket_compiles.values()
    )


def _page_bytes(pool, pages):
    """Concatenated bytes of the given page frames across every
    KV-payload leaf — the read-only checksum for CoW tests."""
    from pytorch_distributed_tpu.serve import page_axis

    chunks = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(pool.cache):
        ax = page_axis(path, leaf)
        if ax is None:
            continue
        arr = np.asarray(jnp.moveaxis(leaf, ax, 0)[np.array(pages)])
        chunks.append(arr.tobytes())
    return b"".join(chunks)


def test_auto_page_size():
    assert auto_page_size(256) == 32
    assert auto_page_size(48) == 16
    assert auto_page_size(40) == 8
    assert auto_page_size(63) == 1  # odd degenerates, still valid
    with pytest.raises(ValueError, match="page_size"):
        EngineConfig(num_slots=1, max_len=64, page_size=24)


def test_prefix_sharing_is_copy_free_and_exact(body):
    """Second request with the same system prompt shares pages
    (refcount, zero prefill for the shared span), its tokens equal the
    solo run, and the shared pages' device bytes never change."""
    model, params = body[0]
    rng = np.random.default_rng(3)
    sys_p = rng.integers(1, 97, size=12).astype(np.int32)
    r1 = Request(
        np.concatenate([sys_p, rng.integers(1, 97, size=3).astype(np.int32)]),
        max_new_tokens=4,
    )
    r2 = Request(
        np.concatenate([sys_p, rng.integers(1, 97, size=5).astype(np.int32)]),
        max_new_tokens=5, temperature=0.8, top_k=9, seed=5,
    )
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=2, max_len=32, prefill_chunk=4, page_size=4,
    ))
    h1 = engine.submit(r1)
    engine.step()  # r1 admitted: capture its page row before release
    r1_pages = list(engine.scheduler.by_slot[h1.slot]._lease.page_row[:3])
    engine.run_until_drained()
    assert h1.tokens == _solo(model, params, r1)
    # r1 retired, but its three full prompt pages (12 tokens / 4) stay
    # registry-held for sharing
    shared_pages = r1_pages
    assert all(engine.pool._ref[pg] == 1 for pg in shared_pages)
    before = _page_bytes(engine.pool, shared_pages)
    h2 = engine.submit(r2)
    # admission must have mapped the registered pages into r2's table
    engine.step()
    lease = engine.scheduler.by_slot[h2.slot]._lease
    assert lease.shared_pages == 3 and lease.skip == 12
    assert list(lease.page_row[:3]) == shared_pages
    engine.run_until_drained()
    assert h2.status is RequestStatus.COMPLETED
    assert h2.tokens == _solo(model, params, r2)
    assert engine.pool.prefix_hits == 1
    assert engine.pool.shared_tokens == 12
    # copy-on-write discipline: the shared pages were never written
    assert _page_bytes(engine.pool, shared_pages) == before
    _assert_bucketed_compiles(engine)
    engine.pool.check_consistency()


@pytest.mark.parametrize("impl", ["gather", "tiled"])
def test_chunk_under_the_view_matches_solo_generate(body, impl, monkeypatch):
    """A prompt chunk runs as the tick does, under a ``PagedView`` of
    its slot's row with the pool as its cache: a prompt of three chunks
    whose last is padded, a second behind a prefix hit on the first's
    whole pages (its one chunk starts mid-prompt, at the shared span's
    end), and a prompt of exactly one chunk each emit solo
    ``generate``'s stream — to the bit under ``"gather"`` (the bucket's
    slab, the dense math), and token for token through the kernel's
    query-tiled body (interpreted; tiles of 16 rows over blocks of 2
    pages, so a chunk spans tiles and blocks)."""
    import sys

    paged = sys.modules["pytorch_distributed_tpu.ops.paged_attention"]
    monkeypatch.setattr(paged, "_IMPL", "kernel" if impl == "tiled" else impl)
    if impl == "tiled":
        monkeypatch.setattr(paged, "_CHUNK_QUERIES", 1)
        monkeypatch.setattr(paged, "_TILE_ROWS", 16)
        monkeypatch.setattr(paged, "_BLOCK_MAX_TOKENS", 8)
    model, params = body[0]
    rng = np.random.default_rng(30)
    ids = lambda n: rng.integers(1, 97, size=n).astype(np.int32)  # noqa: E731
    sys_p = ids(16)
    reqs = [
        Request(np.concatenate([sys_p, ids(3)]), max_new_tokens=5),
        Request(np.concatenate([sys_p, ids(6)]), max_new_tokens=4,
                temperature=0.7, top_k=11, seed=3),
        Request(ids(8), max_new_tokens=3),
    ]
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=2, max_len=48, prefill_chunk=8, page_size=4,
    ))
    handles = []
    for r in reqs:  # one at a time: the second finds the first's pages
        handles.append(engine.submit(r))
        engine.run_until_drained()
    assert engine.pool.prefix_hits == 1 and engine.pool.shared_tokens == 16
    for h, r in zip(handles, reqs):
        assert h.status is RequestStatus.COMPLETED
        assert h.tokens == _solo(model, params, r)
    _assert_bucketed_compiles(engine)
    engine.pool.check_consistency()


def test_page_exhaustion_blocks_head_of_line(gpt2):
    """With pages for only one request in flight, the second queues
    (strict FIFO) until the first retires — and both stay solo-exact."""
    model, params = gpt2
    rng = np.random.default_rng(4)
    r1 = Request(rng.integers(1, 97, size=8).astype(np.int32),
                 max_new_tokens=8)
    r2 = Request(rng.integers(1, 97, size=8).astype(np.int32),
                 max_new_tokens=4)
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=2, max_len=16, prefill_chunk=8, page_size=4,
        num_pages=5,  # one 16-slot request needs 4; two don't fit
    ))
    h1, h2 = engine.submit(r1), engine.submit(r2)
    engine.step()
    assert h1.status is RequestStatus.PREFILLING or h1.tokens
    assert h2.status is RequestStatus.QUEUED  # blocked on pages, not slots
    assert engine.pool.num_free >= 1
    engine.run_until_drained()
    assert h1.tokens == _solo(model, params, r1)
    assert h2.tokens == _solo(model, params, r2)
    engine.pool.check_consistency()


def test_registry_eviction_under_page_pressure(gpt2):
    """Registered prefix pages are evicted LRU when a new admission
    needs their frames — bookkeeping stays consistent throughout."""
    model, params = gpt2
    pool = PagedKVPool(
        model, params, num_slots=2, max_len=16, page_size=4,
        num_pages=6,
    )
    rng = np.random.default_rng(5)
    prompts = []
    # each retiree: P=9 -> span max(9+4, 12) = 13 -> 4 pages, 2 of them
    # full prompt pages that stay registry-held after free()
    for i in range(3):
        ids = rng.integers(1, 97, size=9).astype(np.int32)
        lease = pool.allocate(ids, max_new=4, chunk=4)
        assert lease is not None and lease.shared_pages == 0
        assert lease.n_pages == 4
        pool.register_prefix(lease, ids)   # as if prefill completed
        pool.free(lease.slot)
        prompts.append(ids)
        pool.check_consistency()
        if i == 1:
            # two retirees x 2 registered pages held; 2 frames free
            assert pool.pages_in_use == 4
    # the third retiree's allocate had only 2 free frames for its 4
    # needed and evicted exactly the OLDEST retiree's 2 registry
    # entries (LRU); the two newer retirees' pages remain held
    assert pool.pages_in_use == 4
    again = pool.allocate(prompts[2], max_new=4, chunk=4)
    assert again is not None and again.shared_pages == 2
    pool.check_consistency()
    # the evicted oldest prefix is gone — same prompt, no share (and
    # with `again` holding the last free frames, no pages either)
    gone = pool.allocate(prompts[0], max_new=4, chunk=4)
    assert gone is None
    pool.check_consistency()


def test_mid_flight_eviction_releases_only_private_pages(gpt2):
    """Cancelling one of two prefix-sharing requests mid-decode drops
    its private pages but the shared frames survive for the sibling."""
    model, params = gpt2
    rng = np.random.default_rng(6)
    sys_p = rng.integers(1, 97, size=8).astype(np.int32)

    def mk(new, **kw):
        return Request(
            np.concatenate(
                [sys_p, rng.integers(1, 97, size=3).astype(np.int32)]
            ),
            max_new_tokens=new, **kw,
        )

    engine = ServeEngine(model, params, EngineConfig(
        num_slots=3, max_len=32, prefill_chunk=4, page_size=4,
    ))
    seed_req = mk(2)
    hs = engine.submit(seed_req)
    engine.run_until_drained()  # registers the 2-page system prefix
    assert hs.status is RequestStatus.COMPLETED
    doomed = mk(20, request_id="doomed-paged")
    keeper = mk(6, temperature=0.7, top_p=0.9, seed=8)
    hd, hk = engine.submit(doomed), engine.submit(keeper)
    for _ in range(4):
        engine.step()
    assert hd.status is RequestStatus.DECODING
    shared = [
        pg for pg in engine.scheduler.by_slot[hd.slot]._lease.page_row[:2]
    ]
    assert engine.cancel("doomed-paged")
    engine.run_until_drained()
    assert hd.status is RequestStatus.CANCELLED
    assert hk.status is RequestStatus.COMPLETED
    assert hk.tokens == _solo(model, params, keeper)
    engine.pool.check_consistency()
    # the shared frames are still registry-held (refcount >= 1)
    for pg in shared:
        assert engine.pool._ref[pg] >= 1


def test_spec_greedy_parity_mixed_workload(body):
    """THE speculative acceptance test: greedy requests under a fused
    draft+verify tick emit bit-identical streams to solo generate,
    across slot reuse, chunked prefill, a cancellation and a
    fault-evicted victim — with ONE prefill and ONE tick compile."""
    (model, params), (dmodel, dparams) = body
    rng = np.random.default_rng(7)
    engine = ServeEngine(
        model, params,
        EngineConfig(num_slots=3, max_len=64, prefill_chunk=4,
                     page_size=4),
        spec=SpecConfig(dmodel, dparams, num_draft_tokens=3),
    )

    def mk(p_len, new, **kw):
        return Request(
            prompt_ids=rng.integers(1, 97, size=p_len).astype(np.int32),
            max_new_tokens=new, **kw,
        )

    wave1 = [mk(5, 9), mk(9, 6), mk(3, 12), mk(7, 5)]
    victim = mk(6, 12, request_id="spec-victim")
    doomed = mk(6, 40, request_id="spec-doomed")
    wave2 = [mk(11, 6), mk(2, 7)]
    handles = {}
    with faults.injected(
        "serve.decode:mode=raise,count=1,match=spec-victim"
    ):
        for r in wave1 + [victim, doomed]:
            handles[r.request_id] = engine.submit(r)
        for _ in range(6):
            engine.step()
        for r in wave2:
            handles[r.request_id] = engine.submit(r)
        for _ in range(2):
            engine.step()
        assert engine.cancel("spec-doomed")
        engine.run_until_drained()
    assert handles["spec-victim"].status is RequestStatus.FAILED
    assert handles["spec-doomed"].status is RequestStatus.CANCELLED
    for r in wave1 + wave2:
        h = handles[r.request_id]
        assert h.status is RequestStatus.COMPLETED, h
        assert h.tokens == _solo(model, params, r), r.request_id
    # bounded compile count with pages + speculation: one prefill
    # program (target+draft fused) and one tick program (draft scan +
    # verify fused) per OCCUPIED length bucket, each compiled once
    _assert_bucketed_compiles(engine)
    assert engine.spec_verifies > 0
    assert 0 <= engine.spec_accepted <= engine.spec_drafted
    engine.pool.check_consistency()
    engine.draft_pool.check_consistency()


def test_spec_eos_truncates_inside_accepted_run(gpt2, draft):
    """A request whose eos lands mid-round stops at eos exactly like
    the solo stream (host-side truncation retires the row)."""
    model, params = gpt2
    dmodel, dparams = draft
    rng = np.random.default_rng(8)
    prompt = rng.integers(1, 97, size=5).astype(np.int32)
    ref = _solo(model, params, Request(prompt, max_new_tokens=10))
    eos = ref[4]  # fifth greedy token becomes the stop token
    req = Request(prompt, max_new_tokens=10, eos_id=eos)
    engine = ServeEngine(
        model, params,
        EngineConfig(num_slots=1, max_len=32, prefill_chunk=8,
                     page_size=4),
        spec=SpecConfig(dmodel, dparams, num_draft_tokens=3),
    )
    h = engine.submit(req)
    engine.run_until_drained()
    assert h.status is RequestStatus.COMPLETED
    assert h.tokens == _solo(model, params, req)
    assert h.tokens[-1] == eos
    engine.pool.check_consistency()


def test_spec_sampled_rows_deterministic(gpt2, draft):
    """Sampled requests under speculation follow rejection sampling —
    not token-comparable to generate, but fully deterministic given
    seeds, completing with consistent pools."""
    model, params = gpt2
    dmodel, dparams = draft
    rng = np.random.default_rng(9)
    protos = [
        (rng.integers(1, 97, size=5).astype(np.int32), 8, 0.8, 12, None, 3),
        (rng.integers(1, 97, size=4).astype(np.int32), 6, 0.7, None, 0.9, 11),
        (rng.integers(1, 97, size=6).astype(np.int32), 7, 0.0, None, None, 0),
    ]
    runs = []
    for _ in range(2):
        engine = ServeEngine(
            model, params,
            EngineConfig(num_slots=2, max_len=64, prefill_chunk=4,
                         page_size=4),
            spec=SpecConfig(dmodel, dparams, num_draft_tokens=2),
        )
        hs = [
            engine.submit(Request(
                p, max_new_tokens=n, temperature=t, top_k=k, top_p=tp,
                seed=s,
            ))
            for p, n, t, k, tp, s in protos
        ]
        engine.run_until_drained()
        assert all(h.status is RequestStatus.COMPLETED for h in hs)
        runs.append([h.tokens for h in hs])
        engine.pool.check_consistency()
        engine.draft_pool.check_consistency()
    assert runs[0] == runs[1]
    # the greedy row rides the same tick and must STILL be solo-exact
    p, n = protos[2][0], protos[2][1]
    assert runs[0][2] == _solo(model, params, Request(p, max_new_tokens=n))


def test_spec_full_accept_round_leaves_no_draft_cache_hole():
    """A fully accepted round advances past position L+k — the final
    proposal's K/V must have been cached by the draft fill feed, or the
    draft attends a permanent zero hole forever after (the offline
    loop's documented dfill hazard; acceptance degrades silently while
    emitted tokens stay correct, so only this structural check — every
    position below the write cursor is written — catches it."""
    from pytorch_distributed_tpu.generation import cache_batch_axis
    from pytorch_distributed_tpu.serve import gather_pages

    # damped-tail target + first-block draft (the bench construction):
    # near-perfect agreement makes full-accept rounds routine
    cfg = GPT2Config(
        vocab_size=128, n_positions=96, hidden_size=32, num_layers=2,
        num_heads=2, dropout_rate=0.0,
    )
    model = GPT2LMHead(cfg)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    blocks = params["blocks"]["block"]

    def damp(x):
        if x.ndim < 1 or x.shape[0] != cfg.num_layers:
            return x
        return x.at[1:].multiply(1e-3)

    db = dict(blocks)
    for name in ("attn_out", "mlp_down"):
        db[name] = jax.tree_util.tree_map(damp, blocks[name])
    params = dict(params)
    params["blocks"] = {"block": db}
    dcfg = dataclasses.replace(cfg, num_layers=1)
    dparams = dict(params)
    dparams["blocks"] = {
        "block": jax.tree_util.tree_map(lambda x: x[:1], db)
    }
    dmodel = GPT2LMHead(dcfg)

    k = 3
    engine = ServeEngine(
        model, params,
        EngineConfig(num_slots=1, max_len=48, prefill_chunk=8,
                     page_size=4),
        spec=SpecConfig(dmodel, dparams, num_draft_tokens=k),
    )
    rng = np.random.default_rng(11)
    h = engine.submit(Request(
        rng.integers(1, 128, size=8).astype(np.int32),
        max_new_tokens=20,
    ))
    full_seen = False
    while not h.done and len(h.tokens) < 14:
        before = engine.spec_accepted
        engine.step()
        if engine.spec_accepted - before == k:
            full_seen = True
    assert full_seen, "no fully-accepted round — raise agreement"
    assert not h.done  # the slot (and its pages) must still be live
    slot = h.slot
    L = int(np.asarray(engine._lengths)[slot])
    dense = gather_pages(
        engine.draft_pool.cache, engine._dpt, engine.draft_pool.tails
    )
    for path, leaf in jax.tree_util.tree_leaves_with_path(dense):
        name = getattr(path[-1], "key", None) or str(path[-1])
        if name not in ("cached_key", "cached_value"):
            continue
        ax = cache_batch_axis(path, leaf)
        row = np.moveaxis(np.asarray(leaf), ax, 0)[slot]
        # row: [..., T, H, D] with T now the (ax-removed) leading+1 —
        # reduce every axis except the position axis
        pos_axis = ax  # after removing the batch axis, T sits at ax
        norms = np.abs(row).sum(
            axis=tuple(i for i in range(row.ndim) if i != pos_axis)
        )
        # every position below the write cursor holds REAL draft KV;
        # an unfixed engine leaves position L_old+k all-zero after a
        # full-accept round
        assert (norms[:L] > 0).all(), (
            name, np.nonzero(norms[:L] == 0)[0],
        )
    engine.run_until_drained()
    assert h.status is RequestStatus.COMPLETED


def test_spec_submit_validation(gpt2, draft):
    model, params = gpt2
    dmodel, dparams = draft
    engine = ServeEngine(
        model, params,
        EngineConfig(num_slots=1, max_len=16, prefill_chunk=8,
                     page_size=4),
        spec=SpecConfig(dmodel, dparams, num_draft_tokens=4),
    )
    # 8 + 5 fits max_len 16, but the verify's 4 rejected-draft slots
    # past the horizon do not — refused up front, naming the tail
    with pytest.raises(ValueError, match="speculative-verify"):
        engine.submit(Request(np.ones(8, np.int32), max_new_tokens=5))
    with pytest.raises(ValueError, match="num_draft_tokens"):
        SpecConfig(dmodel, dparams, num_draft_tokens=0)


def test_snapshot_gauges_flow_through_writer(gpt2, draft, tmp_path):
    """Pool occupancy / prefix-hit / speculation gauges ride the same
    split='serve' snapshot records the engine always emitted."""
    model, params = gpt2
    dmodel, dparams = draft
    rng = np.random.default_rng(10)
    path = str(tmp_path / "serve.jsonl")
    writer = MetricsWriter(path)
    sys_p = rng.integers(1, 97, size=8).astype(np.int32)
    engine = ServeEngine(
        model, params,
        EngineConfig(num_slots=2, max_len=32, prefill_chunk=4,
                     page_size=4, telemetry_every=2),
        spec=SpecConfig(dmodel, dparams, num_draft_tokens=2),
        telemetry=ServeTelemetry(writer=writer),
    )
    reqs = [
        Request(
            np.concatenate(
                [sys_p, rng.integers(1, 97, size=3).astype(np.int32)]
            ),
            max_new_tokens=6,
        )
        for _ in range(3)
    ]
    hs = [engine.submit(r) for r in reqs]
    engine.run_until_drained()
    writer.close()
    assert all(h.status is RequestStatus.COMPLETED for h in hs)
    snaps = [
        r for r in read_metrics(path) if r.get("event") == "snapshot"
    ]
    assert snaps
    last = snaps[-1]
    for key in ("pages_in_use", "pages_total", "page_occupancy",
                "prefix_hit_rate", "spec_verifies", "spec_drafted",
                "spec_accepted"):
        assert key in last, key
    assert last["pages_total"] == engine.pool.num_pages
    # the last snapshot precedes any ticks after its cadence boundary
    assert 0 < last["spec_verifies"] <= engine.spec_verifies
    # later requests shared the seeded system prompt
    assert engine.pool.prefix_hits >= 1
    # ...and obs_report's Serving section renders the same gauges
    import io
    import sys as _sys

    _sys.path.insert(0, str(__import__("pathlib").Path(
        __file__).resolve().parent.parent / "scripts"))
    import obs_report

    buf = io.StringIO()
    obs_report.report(None, [path], out=buf)
    text = buf.getvalue()
    assert "== Serving ==" in text
    assert "kv pool: peak" in text and "prefix hit rate" in text
    assert "speculation:" in text and "accepted" in text
    # no modelled byte count is printed: what a tick reads is on its span
    assert "bytes/token" not in text


def test_prefix_shared_requests_builder():
    rng = np.random.default_rng(0)
    reqs = prefix_shared_requests(
        rng, 40, 97, prompt_len=(4, 8), new_tokens=(2, 4),
        prefix_share=0.5, shared_prefix_len=6,
    )
    assert len(reqs) == 40
    heads = {tuple(r.prompt_ids[:6]) for r in reqs if r.prompt_len >= 10}
    # the shared system prompt is ONE head repeated across sharers
    counts = {}
    for r in reqs:
        counts[tuple(r.prompt_ids[:6])] = counts.get(
            tuple(r.prompt_ids[:6]), 0
        ) + 1
    assert max(counts.values()) >= 10  # ~half of 40 share one prefix
    assert heads  # mixed lengths actually got the prefix
    with pytest.raises(ValueError, match="prefix_share"):
        prefix_shared_requests(rng, 2, 97, prefix_share=1.5)
    with pytest.raises(ValueError, match="shared_prefix_len"):
        prefix_shared_requests(rng, 2, 97, prefix_share=0.5)
