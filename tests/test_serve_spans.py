"""The serving loop's span tree (serve/engine.py over runtime/tracing.py).

Armed, every ``engine.step()`` is one tree: ``serve.step`` at the root,
every span recorded during it below it by ``span_id`` / ``parent_id``
and inside its interval; a request's spans share ``request`` from
``serve.submit`` to ``serve.evict``; a tick says the bucket it ran at
and the pages its rows reach. Disarmed, nothing is recorded and a site
is the shared null span. The readers of the tree are checked under
``tests/benchmark/``.
"""

import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from pytorch_distributed_tpu.runtime import tracing
from pytorch_distributed_tpu.serve import (
    EngineConfig,
    Request,
    ServeEngine,
    SpecConfig,
)

pytestmark = [pytest.mark.serve, pytest.mark.obs]

_PAGED = sys.modules["pytorch_distributed_tpu.ops.paged_attention"]

PAGE = 4
CHUNK = 8
SPEC_K = 2
# prompt length, max_new_tokens: ragged, one prompt of three chunks, one
# admitted only when a slot frees (two slots), one done at its first
# token; no two prompts share a first page (no prefix skips a chunk)
MIX = ((6, 5), (19, 3), (9, 4), (5, 1))


def _lm(hidden, layers, seed):
    cfg = GPT2Config(
        vocab_size=97, n_positions=96, hidden_size=hidden,
        num_layers=layers, num_heads=2, dropout_rate=0.0,
    )
    model = GPT2LMHead(cfg)
    params = model.init(
        jax.random.key(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def _engine(mode, **cfg):
    model, params = _lm(32, 2, 0)
    spec = None
    if mode == "spec":
        spec = SpecConfig(*_lm(16, 1, 1), num_draft_tokens=SPEC_K)
    return ServeEngine(model, params, EngineConfig(**{
        "num_slots": 2, "max_len": 64, "prefill_chunk": CHUNK,
        "page_size": PAGE, **cfg,
    }), spec=spec)


class Drive:
    """One traced run of ``MIX`` to the end, and what the host saw."""

    def __init__(self, mode):
        self.mode = mode
        eng = _engine(mode)
        self.W = 1 if mode == "plain" else SPEC_K + 1
        self.lengths_at_tick = []  # pool.lengths of the active rows
        tick = "_decode" if mode == "plain" else "_spec_tick"
        real = getattr(eng, tick)

        def spy(*args):
            self.lengths_at_tick.append([
                int(eng.pool.lengths[s]) for s, _ in eng._decoding_cached
            ])
            return real(*args)

        setattr(eng, tick, spy)
        self.n_steps = 0
        with tracing.enabled() as t:
            self.handles = [
                eng.submit(Request(
                    np.arange(p, dtype=np.int32) + 20 * i + 1,
                    max_new_tokens=n,
                ))
                for i, (p, n) in enumerate(MIX)
            ]
            while eng.has_work():
                eng.step()
                self.n_steps += 1
        self.events = [e for e in t._events if e["ph"] == "X"]

    def named(self, name):
        return [e for e in self.events if e["name"] == name]


@pytest.fixture(scope="module", params=["plain", "spec"])
def drive(request):
    return Drive(request.param)


def test_every_step_is_one_serve_step(drive):
    steps = drive.named("serve.step")
    assert len(steps) == drive.n_steps > 4
    assert all(e["args"]["parent_id"] is None for e in steps)
    ids = [e["args"]["span_id"] for e in drive.events]
    assert len(set(ids)) == len(ids)
    for e in steps:  # the late args say what the step did
        a = e["args"]
        assert a["did"] is bool(a["prefill_chunks"] or a["decoded"])


def test_every_span_of_a_step_hangs_below_it_and_lies_inside_it(drive):
    by_id = {e["args"]["span_id"]: e for e in drive.events}
    inside = 0
    for e in drive.events:
        if e["name"] in ("serve.step", "serve.submit"):
            continue  # submit runs between two steps, at the top
        assert e["name"].startswith("serve.")
        root = e
        while root["args"]["parent_id"] is not None:
            above = by_id[root["args"]["parent_id"]]
            assert above["ts"] <= root["ts"]
            end = above["ts"] + above["dur"] + 1e-3  # ts, dur round to ns
            assert root["ts"] + root["dur"] <= end
            root = above
        assert root["name"] == "serve.step", e
        inside += 1
    assert inside > len(drive.named("serve.step"))
    # the evictions of a tick are the emit loop's children
    emits = {e["args"]["span_id"] for e in drive.named("serve.emit")}
    evicts = drive.named("serve.evict")
    assert sum(e["args"]["parent_id"] in emits for e in evicts) >= 3
    assert all(e["args"]["parent_id"] is None
               for e in drive.named("serve.submit"))


def test_step_counts_its_chunks_and_rows(drive):
    by_parent = {}
    for e in drive.events:
        by_parent.setdefault(e["args"]["parent_id"], []).append(e)
    tick = "serve.decode_tick" if drive.mode == "plain" else "serve.spec_tick"
    for step in drive.named("serve.step"):
        below = by_parent.get(step["args"]["span_id"], [])
        chunks = [e for e in below if e["name"] == "serve.prefill_chunk"]
        ticks = [e for e in below if e["name"] == tick]
        assert step["args"]["prefill_chunks"] == len(chunks)
        assert step["args"]["decoded"] == sum(
            e["args"]["active"] for e in ticks
        )
        assert len(ticks) <= 1
        names = [e["name"] for e in below]
        assert names.count("serve.token_fetch") == len(ticks)
        assert names.count("serve.emit") == len(ticks)


@pytest.mark.parametrize("index", range(len(MIX)))
def test_a_request_is_one_chain_from_submit_to_evict(drive, index):
    h = drive.handles[index]
    prompt, max_new = MIX[index]
    rid = h.request.request_id
    mine = sorted(
        (e for e in drive.events if e["args"].get("request") == rid),
        key=lambda e: e["ts"],
    )
    n_chunks = -(-prompt // CHUNK)
    assert [e["name"] for e in mine] == (
        ["serve.submit", "serve.admit"]
        + ["serve.prefill_chunk"] * n_chunks
        + ["serve.first_token_fetch", "serve.evict"]
    )
    chunks = [e["args"] for e in mine if e["name"] == "serve.prefill_chunk"]
    assert [c["start"] for c in chunks] == [
        i * CHUNK for i in range(n_chunks)
    ]
    assert [c["final"] for c in chunks] == [False] * (n_chunks - 1) + [True]
    # the bucket a chunk ran at covers the positions it can reach
    assert all(c["n_pages"] * PAGE >= c["start"] + CHUNK for c in chunks)
    assert mine[-1]["args"]["status"] == "completed"
    assert len(h.tokens) == max_new


def test_tick_carries_its_bucket_and_the_pages_its_rows_reach(drive):
    tick = "serve.decode_tick" if drive.mode == "plain" else "serve.spec_tick"
    ticks = drive.named(tick)
    assert len(ticks) == len(drive.lengths_at_tick) > 3
    for e, lengths in zip(ticks, drive.lengths_at_tick):
        a = e["args"]
        assert a["active"] == len(lengths)
        # by hand: a row of length L is read at [0, L] and written at
        # [L, L + W): ceil((L + W) / page) pages
        assert a["live_pages"] == sum(
            (n + drive.W + PAGE - 1) // PAGE for n in lengths
        )
        assert a["n_pages"] * PAGE >= max(lengths) + drive.W
        assert a["live_pages"] <= a["active"] * a["n_pages"]
        # off a TPU the attention is the gather impl: every slot's
        # bucket is gathered, whatever its row reaches
        assert a["fetched_pages"] == 2 * a["n_pages"]
        if drive.mode == "spec":
            assert a["k"] == SPEC_K


def test_chunk_carries_the_pages_its_row_reaches(drive):
    """A chunk at ``start`` writes and attends positions up to ``start +
    CHUNK``: ``live_pages`` is the pages under them, by hand; off a TPU
    the attention is the gather impl, which gathers the row's bucket."""
    chunks = [e["args"] for e in drive.named("serve.prefill_chunk")]
    assert len(chunks) == sum(-(-p // CHUNK) for p, _ in MIX)
    for a in chunks:
        assert a["live_pages"] == (a["start"] + CHUNK) // PAGE
        assert a["live_pages"] <= a["fetched_pages"] == a["n_pages"]
    assert any(a["live_pages"] < a["n_pages"] for a in chunks)


def test_fetched_pages_is_the_kernels_own_block_arithmetic(
    monkeypatch, drive
):
    """With the kernel serving the ticks (interpreted here, in blocks
    of 2 pages of 4), ``fetched_pages`` is what the kernel's wrapper
    derives for the tick's lengths — the blocks of each decoding row,
    whole — and the streams are the gather engine's."""
    mode = drive.mode
    monkeypatch.setattr(_PAGED, "_IMPL", "kernel")
    monkeypatch.setattr(_PAGED, "_BLOCK_MAX_TOKENS", 2 * PAGE)
    served = Drive(mode)
    tick = "serve.decode_tick" if mode == "plain" else "serve.spec_tick"
    ticks = served.named(tick)
    assert len(ticks) == len(served.lengths_at_tick) > 3
    whole_blocks = 0
    for e, lengths in zip(ticks, served.lengths_at_tick):
        a = e["args"]
        k = _PAGED.block_pages(PAGE, 32 * 4, a["n_pages"])
        assert k == min(2, a["n_pages"])
        pages, blocks = _PAGED.row_walk(
            np.asarray(lengths), served.W, PAGE, a["n_pages"], k
        )
        assert a["live_pages"] == pages.sum()
        assert a["fetched_pages"] == blocks.sum() * k
        assert a["live_pages"] <= a["fetched_pages"] <= (
            a["active"] * a["n_pages"]
        )
        whole_blocks += a["fetched_pages"] > a["live_pages"]
    assert whole_blocks  # some row ended inside a block
    # a chunk's span says the same of its one row: the pages up to
    # ``start + CHUNK``, and the kernel's blocks of them, whole (8
    # queries are one walk: the tick's body)
    chunks = [e["args"] for e in served.named("serve.prefill_chunk")]
    assert len(chunks) == sum(-(-p // CHUNK) for p, _ in MIX)
    for a in chunks:
        k = _PAGED.block_pages(PAGE, 32 * 4, a["n_pages"])
        pages, blocks = _PAGED.row_walk(
            np.asarray([a["start"]]), CHUNK, PAGE, a["n_pages"], k
        )
        assert a["live_pages"] == pages.sum() == (a["start"] + CHUNK) // PAGE
        assert a["fetched_pages"] == blocks.sum() * k
        assert a["live_pages"] <= a["fetched_pages"] <= a["n_pages"]
    # the 19-token prompt's third chunk reaches 6 pages of a bucket of 8
    assert any(a["fetched_pages"] < a["n_pages"] for a in chunks)
    assert [h.tokens for h in served.handles] == [
        h.tokens for h in drive.handles
    ]


def test_a_tiled_chunk_fetches_its_prefix_once_a_tile(monkeypatch):
    """A chunk of 32 queries goes through the kernel's query-tiled body
    (interpreted; tiles of 16 positions over blocks of 2 pages of 4),
    and each tile walks the row from its first page: ``fetched_pages``
    is the sum over the tiles, by ``tile_walk``, and passes the bucket
    where a chunk has a prefix."""
    C = 32
    request = Request(np.arange(1, 41, dtype=np.int32), max_new_tokens=3)
    exact = _engine("plain", prefill_chunk=C)
    want = exact.submit(request)
    exact.run_until_drained()
    monkeypatch.setattr(_PAGED, "_IMPL", "kernel")
    monkeypatch.setattr(_PAGED, "_BLOCK_MAX_TOKENS", 2 * PAGE)
    monkeypatch.setattr(_PAGED, "_TILE_ROWS", 32)
    assert _PAGED.query_tiles(C, 1, 2, 16) == (2, 16, 2)
    eng = _engine("plain", prefill_chunk=C)
    with tracing.enabled() as t:
        got = eng.submit(request)
        eng.run_until_drained()
    chunks = [e["args"] for e in t._events
              if e["ph"] == "X" and e["name"] == "serve.prefill_chunk"]
    assert [a["start"] for a in chunks] == [0, 32]
    for a in chunks:
        pages, blocks = _PAGED.tile_walk(
            np.asarray([a["start"]]), C, 16, PAGE, a["n_pages"], 2
        )
        assert a["live_pages"] == pages[0, -1] == (a["start"] + C) // PAGE
        assert a["fetched_pages"] == blocks.sum() * 2
    # by hand: the tiles reach 4 and 8 pages, then 12 and 16
    assert [a["fetched_pages"] for a in chunks] == [12, 28]
    assert chunks[1]["fetched_pages"] > chunks[1]["n_pages"] == 16
    assert got.tokens == want.tokens


def test_plain_tick_pages_by_hand():
    """One request of 6 tokens, 5 new, pages of 4: the ticks run at
    lengths 6, 7, 8, 9 and reach 2, 2, 3, 3 pages."""
    eng = _engine("plain")
    with tracing.enabled() as t:
        eng.submit(Request(np.arange(1, 7, dtype=np.int32),
                           max_new_tokens=5))
        eng.run_until_drained()
    ticks = [e["args"] for e in t._events if e["name"] == "serve.decode_tick"]
    assert [a["live_pages"] for a in ticks] == [2, 2, 3, 3]
    assert [a["active"] for a in ticks] == [1, 1, 1, 1]
    assert [a["n_pages"] for a in ticks] == [2, 2, 4, 4]


def test_an_all_greedy_run_has_no_sampling_rows(drive):
    tick = "serve.decode_tick" if drive.mode == "plain" else "serve.spec_tick"
    ticks = drive.named(tick)
    assert ticks and all(e["args"]["sampling_rows"] == 0 for e in ticks)


@pytest.mark.parametrize("mode", ["plain", "spec"])
def test_tick_counts_the_decoding_rows_that_sample(mode):
    """Five requests in five slots, two of them with a temperature and
    done first: ``sampling_rows`` is the host's count of decoding rows
    with ``temperature > 0`` (never a fetch), and falls back to 0 while
    the freed slots still hold their last request's temperature on the
    device, where both ticks' samplers mask it by ``active`` the same
    way (``sample_logits_rows(live=)``, ``_spec_fn``'s ``any_sampled``)."""
    eng = _engine(mode, num_slots=5)
    # (temperature, max_new_tokens): the sampling ones finish early
    mix = ((0.0, 16), (0.8, 8), (0.0, 16), (1.1, 6), (0.0, 16))
    with tracing.enabled() as t:
        handles = [
            eng.submit(Request(
                np.arange(6, dtype=np.int32) + 10 * i + 1,
                max_new_tokens=n, temperature=temp,
                top_p=0.9 if temp else None, seed=i,
            ))
            for i, (temp, n) in enumerate(mix)
        ]
        eng.run_until_drained()
    name = "serve.decode_tick" if mode == "plain" else "serve.spec_tick"
    ticks = [e["args"] for e in t._events if e["name"] == name]
    counts = [a["sampling_rows"] for a in ticks]
    assert all(0 <= c <= a["active"] for c, a in zip(counts, ticks))
    assert max(counts) == 2 and counts[-1] == 0
    # once down, it stays down: nothing new was admitted
    first_zero = counts.index(0, counts.index(2))
    assert set(counts[first_zero:]) == {0}
    if mode == "plain":
        # by hand: a request's first token comes from its prefill, every
        # other from one tick, so the two sampling requests ride
        # (8 - 1) + (6 - 1) ticks between them
        assert sum(counts) == 12
    # the stale rows are really there: the freed slots kept their temps
    stale = np.asarray(eng._temps)
    assert (stale > 0).sum() == 2 and all(h.done for h in handles)


def test_sweep_span_only_when_a_sweep_has_work():
    eng = _engine("plain")
    with tracing.enabled() as t:
        h = eng.submit(Request(np.arange(1, 7, dtype=np.int32),
                               max_new_tokens=8))
        eng.step()
        eng.step()
        assert not [e for e in t._events if e["name"] == "serve.sweep"]
        eng.cancel(h.request.request_id)
        eng.step()
    sweeps = [e for e in t._events if e["name"] == "serve.sweep"]
    evicts = [e for e in t._events if e["name"] == "serve.evict"]
    assert len(sweeps) == 1 and len(evicts) == 1
    assert evicts[0]["args"]["parent_id"] == sweeps[0]["args"]["span_id"]
    assert evicts[0]["args"]["status"] == "cancelled"


def test_prefill_tier_reads_its_first_token_inside_a_span():
    eng = _engine("plain", role="prefill")
    with tracing.enabled() as t:
        h = eng.submit(Request(np.arange(1, 12, dtype=np.int32),
                               max_new_tokens=4))
        while eng.has_work():
            eng.step()
    assert h.status.value == "migrated" and len(eng.outbox) == 1
    fetch = [e for e in t._events if e["name"] == "serve.first_token_fetch"]
    assert [e["args"]["request"] for e in fetch] == [h.request.request_id]
    assert not [e for e in t._events if e["name"] == "serve.decode_tick"]


@pytest.mark.parametrize("mode", ["plain", "spec"])
def test_an_armed_run_records_no_counter_event(mode):
    """Snapshots at every step: what a tick reads is on its span
    (``live_pages``, ``fetched_pages``), and no ``C`` event is left."""
    eng = _engine(mode, telemetry_every=1)
    with tracing.enabled() as t:
        eng.submit(Request(np.arange(1, 7, dtype=np.int32),
                           max_new_tokens=3))
        eng.run_until_drained()
    assert not [e["name"] for e in t._events if e["ph"] == "C"]
    ticks = [e for e in t._events
             if e["name"] in ("serve.decode_tick", "serve.spec_tick")]
    assert ticks and all(
        0 < e["args"]["live_pages"] <= e["args"]["fetched_pages"]
        for e in ticks
    )


def test_disarmed_step_records_nothing():
    tracing.clear()
    eng = _engine("plain")
    eng.submit(Request(np.arange(1, 7, dtype=np.int32), max_new_tokens=3))
    eng.run_until_drained()
    assert tracing.get() is None
    assert tracing.span("x") is tracing._NULL_SPAN
    assert tracing.span("x", k=1) is tracing._NULL_SPAN
    with tracing.span("x") as sp:
        assert sp.set(did=True) is None  # accepted and ignored
    # arming later starts from nothing: the disarmed run left no stack
    with tracing.enabled() as t:
        eng.step()
    plan, step = t._events  # an idle step: nothing to plan, no tick
    assert (plan["name"], step["name"]) == ("serve.prefill_plan",
                                            "serve.step")
    assert plan["args"] == {"span_id": 2, "parent_id": 1}
    assert step["args"] == {"span_id": 1, "parent_id": None, "did": False,
                            "prefill_chunks": 0, "decoded": 0}


# -- the tracer's side -------------------------------------------------------
def test_parent_is_the_enclosing_span_of_the_same_thread():
    seen = {}

    def other():
        with tracing.span("t.outer"):
            with tracing.span("t.inner"):
                pass

    with tracing.enabled() as t:
        with tracing.span("outer", phase="a") as outer:
            th = threading.Thread(target=other)
            th.start()
            th.join(timeout=10)
            assert not th.is_alive()
            with tracing.span("inner"):
                pass
            outer.set(late=3)
        with tracing.span("next"):
            pass
    for e in t._events:
        seen[e["name"]] = e["args"]
    assert seen["outer"]["parent_id"] is None
    assert seen["outer"]["phase"] == "a" and seen["outer"]["late"] == 3
    assert seen["inner"]["parent_id"] == seen["outer"]["span_id"]
    # another thread's spans start a tree of their own
    assert seen["t.outer"]["parent_id"] is None
    assert seen["t.inner"]["parent_id"] == seen["t.outer"]["span_id"]
    assert seen["next"]["parent_id"] is None
    assert len({a["span_id"] for a in seen.values()}) == 5


def test_a_span_that_raises_still_leaves_the_stack():
    with tracing.enabled() as t:
        with pytest.raises(ValueError):
            with tracing.span("a"):
                with tracing.span("b"):
                    raise ValueError("x")
        with tracing.span("c"):
            pass
    args = {e["name"]: e["args"] for e in t._events}
    assert args["b"]["parent_id"] == args["a"]["span_id"]
    assert args["c"]["parent_id"] is None


def test_a_site_s_shared_args_dict_is_copied_per_span():
    """``parallel/ddp.py`` hands one dict to every span of its site and
    ``parallel/overlap.py`` hands ``None``: each span still gets ids of
    its own, and the caller's dict is left as it was."""
    shared = {"leaves": 7}
    with tracing.enabled() as t:
        for _ in range(2):
            with tracing._Span(t, "comm.sync_grads", shared):
                with tracing._Span(t, "comm.sync_drain", None):
                    pass
    assert shared == {"leaves": 7}
    grads = [e["args"] for e in t._events if e["name"] == "comm.sync_grads"]
    drains = [e["args"] for e in t._events if e["name"] == "comm.sync_drain"]
    assert [a["span_id"] for a in grads] == [1, 3]
    assert [a["parent_id"] for a in drains] == [1, 3]
    assert all(a["leaves"] == 7 for a in grads)
