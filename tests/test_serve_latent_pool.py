"""The page pool on LATENT pages (``models/deepseek_v3.py``): one leaf a
layer, a dense layer's unstacked leaf beside the scanned stack's, the
pool following each leaf's geometry and not its name.

Toy sizes, float32, the paged kernel interpreted as in
``tests/test_paged_attention.py``: a prompt prefilled in chunks and then
decoded through the pool against the plain reference's full forward
(logits); prefix sharing, the frame signature and the migration codec on
latent pages; and ``tests/test_serve_pool_inplace.py``'s walk of the
jaxprs — no program moves a plane of the pool."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pytorch_distributed_tpu.ops  # noqa: F401 — registers the submodules
from perfbench.harness import cells, kind_serve, weights as W
from pytorch_distributed_tpu.generation import decode_step_body
from pytorch_distributed_tpu.ops.paged_attention import PagedView, paged_view
from pytorch_distributed_tpu.runtime import precision, tracing
from pytorch_distributed_tpu.serve import EngineConfig, ServeEngine
from pytorch_distributed_tpu.serve.kv_slots import (
    extract_frames,
    frame_nbytes,
    frame_signature,
    page_axis,
    splice_frames,
)
from pytorch_distributed_tpu.serve.scheduler import Request

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_serve_pool_inplace as inplace  # noqa: E402

pytestmark = pytest.mark.serve

_PAGED = sys.modules["pytorch_distributed_tpu.ops.paged_attention"]
FULL = precision.Policy(
    param_dtype=jnp.float32, compute_dtype=jnp.float32,
    output_dtype=jnp.float32,
)
REF = cells.load_module(os.path.join(
    cells.ROOT, "perfbench", "references", "gigachat3.1-702b-ep16-l5.py"
))
SLOTS, MAX_LEN, PAGE, PAGES, CHUNK = 4, 64, 8, 40, 16


@pytest.fixture
def toy(monkeypatch):
    """(engine, cfg, seed's weights): the cell's toy configuration on a
    float32 engine with the kernel on (interpreted off the chip)."""
    monkeypatch.setattr(_PAGED, "_IMPL", "kernel")
    cell = cells.Cell("gigachat-serve-sat")
    cfg = dict(cell.config)
    cfg.update(cell.spec["rehearsal"]["config"])
    cfg["precision"] = dict(cfg["precision"], param_dtype="float32")
    fam = cell.family()
    sw = kind_serve.SeedWeights(11, fam, cfg)
    with precision.use_policy(FULL):
        model = fam.build_model(cfg)
        params = W.program_params(sw.key, fam, cfg)
        engine = ServeEngine(model, params, EngineConfig(
            num_slots=SLOTS, max_len=MAX_LEN, prefill_chunk=CHUNK,
            page_size=PAGE, num_pages=PAGES, prefix_cache=True,
        ))
        yield engine, cfg, sw


def test_the_pool_follows_the_leaves_geometry(toy):
    engine, cfg, _ = toy
    leaves = {
        jax.tree_util.keystr(p): x
        for p, x in jax.tree_util.tree_leaves_with_path(engine.pool.cache)
    }
    pools = {k: x.shape for k, x in leaves.items() if x.ndim >= 3}
    # the dense layer's leaf unstacked, the expert stack's with its [L];
    # 64 + 16 cached values a token in one 128-lane frame
    assert sorted(pools.values()) == [
        (2, PAGES + 1, PAGE, 128), (PAGES + 1, PAGE, 128),
    ]
    for path, x in jax.tree_util.tree_leaves_with_path(engine.pool.cache):
        assert page_axis(path, x) == (x.ndim - 3 if x.ndim >= 3 else None)
    sig = frame_signature(engine.pool.cache, PAGE)
    assert sig == (
        "ps=8|cached_latent:(8, 128):float32|cached_latent:(2, 8, 128):float32"
    )
    assert engine.migration_signature == sig
    assert frame_nbytes(engine.pool.cache) == 3 * PAGE * 128 * 4


def test_chunked_prefill_then_decode_is_the_references_forward(toy):
    """The engine's own chunk program body and the tick's body (the
    absorbed kernel over the pool), driven by hand so that LOGITS come
    back: every position against the reference's full forward."""
    engine, cfg, sw = toy
    model, params, pool = engine.model, engine.params, engine.pool
    P, new = 37, 6
    ids = np.random.default_rng(2).integers(1, cfg["vocab_size"], P + new)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(REF.served_logits(cfg, sw, ids, 0))
    # rows 1 and 2 hold the same sequence on their own pages; row 0 and
    # row 3 stay free (dropped writes, null pages)
    pt = np.zeros((SLOTS, MAX_LEN // PAGE), np.int32)
    pt[1, :6] = np.arange(1, 7)
    pt[2, :6] = np.arange(11, 17)
    pt = jnp.asarray(pt)
    cache = pool.cache
    got = np.zeros_like(want)
    with precision.use_policy(FULL):
        for slot in (1, 2):
            for start in range(0, P, CHUNK):
                n = min(CHUNK, P - start)
                chunk = np.zeros((1, CHUNK), np.int32)
                chunk[0, :n] = ids[start:start + n]
                logits, cache, routed = engine._prefill_chunk_body(
                    model, params, pool, cache, pt, jnp.asarray(chunk),
                    slot, start, 8,
                )
                got[start:start + n] = np.asarray(logits[0, :n])
                assert routed.shape == (2, 3)  # two expert layers
        active = jnp.asarray([False, True, True, False])
        for t in range(P, P + new):
            lengths = jnp.asarray([0, t, t, 5], jnp.int32)
            toks = jnp.full(SLOTS, int(ids[t]), jnp.int32)
            with paged_view(PagedView(
                page_tables=pt, keep=active, page_size=PAGE,
            )):
                last, cache, _ = decode_step_body(
                    model, params, cache, toks, cache_len=MAX_LEN,
                    positions=lengths[:, None], write_pos=lengths,
                    with_intermediates=True,
                )
            np.testing.assert_allclose(last[1], last[2], atol=1e-6)
            got[t] = np.asarray(last[1])
    assert np.abs(got - want).max() < 2e-5
    # the free rows wrote nothing: the null page and the pages nobody
    # owns are still zero
    for leaf in jax.tree_util.tree_leaves(cache):
        if leaf.ndim >= 3:
            frames = np.asarray(leaf).reshape(-1, PAGES + 1, PAGE, 128)
            assert not frames[:, 0].any() and not frames[:, 20:].any()
            assert frames[:, 1:6].any() and frames[:, 11:16].any()


def test_a_latent_chunk_decodes_its_gathered_rows(toy):
    """A chunk under the view DECODES the row's bucket of latents (one
    gather of it a leaf, then the dense arithmetic), whatever the
    table's width: the same chunk behind the same prefix at a bucket of
    8 pages and of 16 gives the same logits, and neither program holds
    an attention kernel. A speculative verify's few queries a row run
    absorbed through the kernel, as the tick does: ``ops`` tells the
    two apart (``is_chunk``), not the model."""
    small, cfg, _ = toy
    with precision.use_policy(FULL):
        engine = ServeEngine(small.model, small.params, EngineConfig(
            num_slots=SLOTS, max_len=2 * MAX_LEN, prefill_chunk=CHUNK,
            page_size=PAGE, num_pages=PAGES,
        ))
        ids = np.random.default_rng(3).integers(1, cfg["vocab_size"], 48)
        pt = np.zeros((SLOTS, 2 * MAX_LEN // PAGE), np.int32)
        pt[1, :6] = np.arange(1, 7)
        pt = jnp.asarray(pt)
        logits, texts = {}, {}
        for n_pages in (8, 16):
            cache = engine.pool.cache
            for start in range(0, 48, CHUNK):
                args = (engine.model, engine.params, engine.pool, cache, pt,
                        jnp.asarray(ids[None, start:start + CHUNK]), 1,
                        start)
                out, cache, _ = engine._prefill_chunk_body(*args, n_pages)
            logits[n_pages] = np.asarray(out)
            texts[n_pages] = str(jax.make_jaxpr(
                lambda c, i: engine._prefill_chunk_body(
                    args[0], args[1], args[2], c, pt, i, 1, 32, n_pages
                )[0]
            )(cache, args[5]))

        def verify(cache, toks, lengths):
            with paged_view(PagedView(
                page_tables=pt, keep=jnp.ones(SLOTS, bool), page_size=PAGE,
            )):
                return engine.model.apply(
                    {"params": engine.params, "cache": cache}, toks,
                    decode=True, cache_len=2 * MAX_LEN, mutable=["cache"],
                    positions=lengths[:, None] + jnp.arange(5),
                    write_pos=lengths,
                )[0]

        texts["verify"] = str(jax.make_jaxpr(verify)(
            cache, jnp.ones((SLOTS, 5), jnp.int32),
            jnp.zeros(SLOTS, jnp.int32),
        ))
    assert np.abs(logits[8] - logits[16]).max() < 2e-5
    assert np.abs(logits[8]).max() > 1e-2
    # the expert layer's kernel is there either way
    for n_pages in (8, 16):
        assert "name=paged_" not in texts[n_pages]
        assert "gather[" in texts[n_pages]
    assert texts["verify"].count("name=paged_attention") == 2  # both leaves
    assert "name=paged_prefill" not in texts["verify"]


def test_prefix_sharing_and_migration_frames_on_latent_pages(toy):
    engine, cfg, _ = toy
    rng = np.random.default_rng(4)
    doc = rng.integers(1, cfg["vocab_size"], 24)
    tails = [rng.integers(1, cfg["vocab_size"], 5 + i) for i in range(3)]
    prompts = [np.concatenate([doc, t]).astype(np.int32) for t in tails]
    tracer = tracing.configure(None)
    try:
        first = engine.submit(Request(prompts[0], max_new_tokens=4))
        engine.run_until_drained()
        rest = [engine.submit(Request(p, max_new_tokens=4))
                for p in prompts[1:]]
        engine.run_until_drained()
        events = list(tracer._events)
    finally:
        tracing.clear()
    assert all(h.status.value == "completed" for h in [first] + rest)
    # three whole pages of the document were served from shared pages
    assert engine.pool.prefix_hits == 2
    assert engine.pool.shared_tokens == 2 * 24
    engine.pool.check_consistency()
    # sharing changed no token: a pool without the registry agrees
    with precision.use_policy(FULL):
        plain = ServeEngine(engine.model, engine.params, EngineConfig(
            num_slots=SLOTS, max_len=MAX_LEN, prefill_chunk=CHUNK,
            page_size=PAGE, num_pages=PAGES, prefix_cache=False,
        ))
        again = [plain.submit(Request(p, max_new_tokens=4)) for p in prompts]
        plain.run_until_drained()
    assert [h.tokens for h in [first] + rest] == [h.tokens for h in again]
    # the routing counters rode down with the tokens, onto both spans
    for name in ("serve.decode_tick", "serve.prefill_chunk"):
        args = [e["args"] for e in events if e["name"] == name]
        assert args and all(
            len(a["expert_pairs"]) == len(a["experts_hit"])
            == len(a["expert_peak"]) == 2 for a in args
        ), name
        assert all(
            0 <= hit <= 8 and peak <= pairs
            for a in args for pairs, hit, peak in zip(
                a["expert_pairs"], a["experts_hit"], a["expert_peak"])
        )
    ticks = [e["args"] for e in events if e["name"] == "serve.decode_tick"]
    assert all("live_pages" in a and a["live_pages"] >= 1 for a in ticks)
    # frames of latent pages splice losslessly into a pool of the same
    # geometry (the migration codec and the prefix store's payloads)
    pages = [1, 2, 3]
    payload = extract_frames(engine.pool.cache, pages)
    assert payload.size == 3 * frame_nbytes(engine.pool.cache)
    assert payload.any()
    spliced = splice_frames(plain.pool.cache, [7, 8, 9], payload)
    np.testing.assert_array_equal(
        extract_frames(spliced, [7, 8, 9]), payload
    )
    with pytest.raises(ValueError, match="payload"):
        splice_frames(plain.pool.cache, [7, 8], payload)


@pytest.mark.parametrize("impl", ["gather", "kernel"])
def test_no_program_moves_a_plane_of_the_latent_pool(toy, impl, monkeypatch):
    """``tests/test_serve_pool_inplace.py``'s walk over the tick and the
    chunk programs of the latent pool: a plane is touched by scatter,
    loop carry and the read alone — the unstacked dense leaf and the
    stacked one alike."""
    monkeypatch.setattr(_PAGED, "_IMPL", impl)
    small, _, _ = toy
    # a pool whose plane outweighs every weight of the toy model, as
    # the dense models' test has it
    with precision.use_policy(FULL):
        engine = ServeEngine(small.model, small.params, EngineConfig(
            num_slots=SLOTS, max_len=MAX_LEN, prefill_chunk=CHUNK,
            page_size=PAGE, num_pages=1023,
        ))
    plane = min(
        x.size // (x.shape[0] if x.ndim == 4 else 1)
        for x in jax.tree_util.tree_leaves(engine.pool.cache) if x.ndim >= 3
    )
    monkeypatch.setattr(inplace, "CHUNK", CHUNK)
    for name, (fn, args) in inplace._programs(engine).items():
        jaxpr = jax.make_jaxpr(fn, static_argnums=(len(args) - 1,))(*args)
        faults = inplace._faults(jaxpr.jaxpr, plane, [])
        assert not faults, f"{name} moves the pool:\n" + "\n".join(faults)
        text = str(jaxpr)
        kernel = impl == "kernel" and "prefill" not in name
        assert "scatter" in text
        assert ("pallas_call" if kernel else "gather") in text
