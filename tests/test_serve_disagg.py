"""Disaggregated serving (serve/, round 18): tiers, KV migration,
router, prefix registry.

Contracts under test, on top of test_serve.py / test_serve_paged.py:

* roles change which programs a request REACHES, never their math: a
  prefill-role engine fills pages and ships ``MigrationFrame``s, a
  decode-role engine takes work via ``inject_migration`` only, and the
  finished stream — greedy AND sampled — is bit-identical to the solo
  engine's (the page-table splice plus the rng re-derivation on the
  receiver reproduce the solo tick state exactly);
* the wire format is fingerprint-guarded end to end: a receiver whose
  pool geometry disagrees (page size, cache dtype, model shape), or a
  payload damaged in flight, is refused BEFORE any bytes are used —
  at the codec layer and again at ``inject_migration``;
* int8 pools migrate their native payload (int8 K/V + f32 scale
  sidecars) with exact byte accounting: ``payload.nbytes == n_pages *
  frame_nbytes(cache)``, and the native frame costs <= 0.55x its f32
  equivalent;
* the cross-engine prefix registry prefills a shared system prompt
  ONCE per fleet (put counts pinned), peers adopt published pages
  instead of recomputing, refcounts survive engine churn
  (``release_holder``), and adoption never changes tokens;
* the router is a deterministic pure function of the telemetry record
  stream: total-order picks, evict-and-replay on ``serve.engine_loss``
  with final streams bit-identical to the no-fault run, and a fleet
  that stays duck-compatible with ``loadgen.drive``.

The 2-process worker (``hostring_workers.disagg_migration_worker``)
runs the same hand-off over the ring's REAL P2P mailboxes.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from pytorch_distributed_tpu.runtime import faults
from pytorch_distributed_tpu.serve import (
    EngineConfig,
    GaugeBoard,
    InProcPrefixStore,
    MigrationError,
    Request,
    RequestStatus,
    Router,
    ServeEngine,
    SpecConfig,
    decode_frame,
    encode_frame,
    extract_frames,
    frame_f32_nbytes,
    frame_nbytes,
    frame_signature,
    roundtrip_frame,
)
from tests import hostring_workers

pytestmark = pytest.mark.disagg


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
def gpt2_int8():
    cfg = GPT2Config(
        vocab_size=97, n_positions=96, hidden_size=32, num_layers=2,
        num_heads=2, dropout_rate=0.0, kv_cache_quantize="int8",
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


ECFG = dict(num_slots=4, max_len=96, prefill_chunk=8)


def _requests(n=6, seed=7, vocab=97, new=8):
    """Mixed greedy/sampled requests with ragged prompt lengths."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        plen = int(rng.integers(3, 25))
        out.append(Request(
            rng.integers(1, vocab, size=plen).astype(np.int32),
            max_new_tokens=new, request_id=f"r{seed}-{i}",
            temperature=(0.9 if i % 2 else 0.0),
            top_k=(20 if i % 2 else None), seed=1000 + i,
        ))
    return out


def _solo_streams(model, params, reqs, **cfg):
    eng = ServeEngine(model, params, EngineConfig(**(ECFG | cfg)))
    hs = [eng.submit(r) for r in reqs]
    eng.run_until_drained()
    assert all(h.status is RequestStatus.COMPLETED for h in hs)
    return {r.request_id: h.tokens for r, h in zip(reqs, hs)}


def _migrate_all(pre, dec):
    """Hand every prefill outbox frame to the decode engine through the
    full wire codec, then drain — the router's loop, unrolled. Returns
    the decode-side handles by request id."""
    pre.run_until_drained()
    got = {}
    while pre.outbox:
        frame = pre.outbox.popleft()
        wire, _ = roundtrip_frame(frame, dec.migration_signature)
        got[frame.request_id] = dec.inject_migration(wire)
    dec.run_until_drained()
    return got


# -- roles -----------------------------------------------------------------
class TestRoles:
    def test_bad_role_refused(self):
        with pytest.raises(ValueError, match="role"):
            EngineConfig(role="mixed")

    def test_decode_role_refuses_submit(self, gpt2):
        eng = ServeEngine(*gpt2, EngineConfig(role="decode", **ECFG))
        with pytest.raises(RuntimeError, match="decode"):
            eng.submit(_requests(1)[0])

    def test_prefill_role_refuses_inject(self, gpt2):
        pre = ServeEngine(*gpt2, EngineConfig(role="prefill", **ECFG))
        h = pre.submit(_requests(1, seed=11)[0])
        pre.run_until_drained()
        assert h.status is RequestStatus.MIGRATED
        frame = pre.outbox.popleft()
        with pytest.raises(RuntimeError, match="prefill"):
            pre.inject_migration(frame)

    def test_spec_with_role_refused(self, gpt2, draft):
        spec = SpecConfig(*draft, num_draft_tokens=2)
        with pytest.raises(ValueError, match="spec"):
            ServeEngine(
                *gpt2, EngineConfig(role="prefill", **ECFG), spec=spec
            )

    def test_spec_with_store_refused(self, gpt2, draft):
        spec = SpecConfig(*draft, num_draft_tokens=2)
        with pytest.raises(ValueError, match="spec"):
            ServeEngine(
                *gpt2, EngineConfig(**ECFG), spec=spec,
                prefix_store=InProcPrefixStore(),
            )

    @pytest.mark.parametrize("body", ["gpt2-L2", "gpt2-L1", "llama-gqa-L3"])
    def test_migration_parity_greedy_and_sampled(self, gpt2, body):
        """THE correctness gate: prefill -> wire -> decode streams are
        bit-identical to the solo engine's, greedy and sampled alike.
        Frames are whole lane-dense page frames of every layer's
        plane: both ends commit to the same ``frame_signature`` and the
        bytes splice losslessly, at any depth, MHA or GQA."""
        from tests.test_serve_paged import make_body

        if body != "gpt2-L2":
            gpt2 = make_body(body)
        reqs = _requests(6, seed=21)
        want = _solo_streams(*gpt2, reqs)
        pre = ServeEngine(*gpt2, EngineConfig(role="prefill", **ECFG))
        dec = ServeEngine(*gpt2, EngineConfig(role="decode", **ECFG))
        assert pre.migration_signature == dec.migration_signature
        assert frame_signature(
            pre.pool.cache, pre.pool.page_size
        ) in pre.migration_signature
        hs = {r.request_id: pre.submit(r) for r in reqs}
        pre.run_until_drained()
        assert all(
            h.status is RequestStatus.MIGRATED for h in hs.values()
        )
        assert pre.migrated_out == len(reqs)
        got = {}
        while pre.outbox:
            frame = pre.outbox.popleft()
            wire, _ = roundtrip_frame(frame, dec.migration_signature)
            got[frame.request_id] = dec.inject_migration(wire)
        dec.run_until_drained()
        assert dec.migrated_in == len(reqs)
        for rid, h in got.items():
            assert h.status is RequestStatus.COMPLETED, (rid, h.error)
            assert h.tokens == want[rid], rid
        # the shipped first token heads the decode stream: emission is
        # exactly-once across the hand-off
        for rid, h in got.items():
            assert len(h.tokens) == len(want[rid])


# -- wire format -----------------------------------------------------------
class TestWire:
    def _frame(self, gpt2, seed=31):
        pre = ServeEngine(*gpt2, EngineConfig(role="prefill", **ECFG))
        pre.submit(_requests(1, seed=seed)[0])
        pre.run_until_drained()
        return pre, pre.outbox.popleft()

    def test_codec_roundtrip(self, gpt2):
        pre, frame = self._frame(gpt2)
        arrays = encode_frame(frame)
        back = decode_frame(
            arrays[1], arrays[2], arrays[3], pre.migration_signature
        )
        assert back.request == frame.request
        assert back.first_token == frame.first_token
        assert back.prompt_len == frame.prompt_len
        assert back.n_pages == frame.n_pages
        assert back.signature == frame.signature
        assert np.array_equal(back.payload, frame.payload)

    def test_codec_refuses_wrong_signature(self, gpt2):
        _, frame = self._frame(gpt2, seed=32)
        arrays = encode_frame(frame)
        with pytest.raises(MigrationError, match="fingerprint"):
            decode_frame(
                arrays[1], arrays[2], arrays[3], "ps=1|bogus:(1,):int8"
            )

    def test_codec_refuses_damaged_payload(self, gpt2):
        pre, frame = self._frame(gpt2, seed=33)
        arrays = encode_frame(frame)
        arrays[3] = arrays[3].copy()
        arrays[3][0] ^= 0xFF
        with pytest.raises(MigrationError, match="fingerprint"):
            decode_frame(
                arrays[1], arrays[2], arrays[3], pre.migration_signature
            )

    def test_inject_refuses_mixed_geometry(self, gpt2):
        """A fleet mixing page sizes is refused at inject time even when
        the frame object is handed over directly (no codec hop)."""
        pre, frame = self._frame(gpt2, seed=34)
        dec = ServeEngine(
            *gpt2, EngineConfig(role="decode", **(ECFG | {"page_size": 4}))
        )
        assert dec.migration_signature != pre.migration_signature
        with pytest.raises(MigrationError, match="geometry"):
            dec.inject_migration(frame)

    def test_inject_refuses_inconsistent_page_count(self, gpt2):
        pre, frame = self._frame(gpt2, seed=35)
        dec = ServeEngine(*gpt2, EngineConfig(role="decode", **ECFG))
        bad = dataclasses.replace(frame, n_pages=frame.n_pages + 1)
        with pytest.raises(MigrationError, match="page"):
            dec.inject_migration(bad)

    def test_int8_payload_accounting(self, gpt2_int8):
        """int8 pools ship native bytes with EXACT accounting: the
        payload is n_pages frames, each frame_nbytes long, and the
        native frame undercuts the f32 frame by the pinned ratio."""
        pre = ServeEngine(
            *gpt2_int8, EngineConfig(role="prefill", **ECFG)
        )
        per_page = frame_nbytes(pre.pool.cache)
        f32_page = frame_f32_nbytes(pre.pool.cache)
        # D=16: (1 + 4/16) / 4 = 0.3125x — comfortably under 0.55
        assert per_page * 100 <= 55 * f32_page, (per_page, f32_page)
        reqs = _requests(3, seed=41)
        for r in reqs:
            pre.submit(r)
        pre.run_until_drained()
        dec = ServeEngine(
            *gpt2_int8, EngineConfig(role="decode", **ECFG)
        )
        ps = pre.pool.page_size
        while pre.outbox:
            frame = pre.outbox.popleft()
            assert frame.n_pages == -(-frame.prompt_len // ps)
            assert frame.payload.nbytes == frame.n_pages * per_page
            wire, nbytes = roundtrip_frame(
                frame, dec.migration_signature
            )
            assert nbytes > frame.payload.nbytes  # framing overhead
            h = dec.inject_migration(wire)
            dec._drain_inject_backlog()
            # splice landed the wire bytes verbatim (pre-tick)
            got = extract_frames(
                dec.pool.cache, list(h._lease.page_row[: frame.n_pages])
            )
            assert got.tobytes() == np.asarray(
                frame.payload, np.uint8
            ).tobytes()
        dec.run_until_drained()

    def test_int8_migration_parity(self, gpt2_int8):
        """Lossless codec + splice: int8 caches migrate bit-exactly."""
        reqs = _requests(4, seed=42)
        want = _solo_streams(*gpt2_int8, reqs)
        pre = ServeEngine(
            *gpt2_int8, EngineConfig(role="prefill", **ECFG)
        )
        dec = ServeEngine(
            *gpt2_int8, EngineConfig(role="decode", **ECFG)
        )
        for r in reqs:
            pre.submit(r)
        got = _migrate_all(pre, dec)
        for rid, toks in want.items():
            assert got[rid].status is RequestStatus.COMPLETED, rid
            assert got[rid].tokens == toks, rid

    def test_signature_names_geometry(self, gpt2, gpt2_int8):
        s_f32 = frame_signature(
            ServeEngine(*gpt2, EngineConfig(**ECFG)).pool.cache, 8
        )
        s_int8 = frame_signature(
            ServeEngine(*gpt2_int8, EngineConfig(**ECFG)).pool.cache, 8
        )
        assert s_f32 != s_int8
        assert "ps=8" in s_f32
        # a frame is every layer's [ps, H * D] rows of one page (the pool
        # here has 32-token pages), heads folded lane-dense as stored
        assert "cached_key:(2, 32, 32):" in s_f32


# -- prefix registry -------------------------------------------------------
class TestPrefixStore:
    def test_first_writer_wins(self):
        store = InProcPrefixStore(signature="sig")
        a = np.arange(16, dtype=np.uint8)
        assert store.put(b"k1", a, "e0", "sig")
        assert not store.put(b"k1", a * 0, "e1", "sig")  # dup: a no-op
        assert store.stats()["dup_puts"] == 1
        got = store.get(b"k1", "e1")
        assert np.array_equal(got, a)  # first writer stays canonical
        assert store.stats()["hits"] == 1

    def test_signature_mismatch_refused(self):
        store = InProcPrefixStore(signature="sig")
        with pytest.raises(ValueError, match="geometry"):
            store.put(b"k", np.zeros(4, np.uint8), "e0", "other-sig")

    def test_holder_pins_survive_pressure(self):
        """Pinned entries are never evicted; releasing the holder frees
        them for LRU reclaim — refcounts across engine churn."""
        store = InProcPrefixStore(capacity_pages=2, signature="sig")
        store.put(b"a", np.zeros(32, np.uint8), "e0", "sig")
        store.put(b"b", np.zeros(32, np.uint8), "e1", "sig")
        # every entry pinned: a third put must refuse, never evict a pin
        assert not store.put(b"c", np.zeros(32, np.uint8), "e2", "sig")
        assert b"a" in store and b"b" in store
        assert store.pinned(b"a") == 1
        assert store.release_holder("e0") == 1
        assert store.pinned(b"a") == 0
        assert store.put(b"c", np.zeros(32, np.uint8), "e2", "sig")
        assert b"a" not in store  # the unpinned LRU entry made room
        assert b"b" in store
        assert store.stats()["evictions"] == 1

    def test_store_with_spec_refused(self, gpt2, draft):
        spec = SpecConfig(*draft, num_draft_tokens=2)
        with pytest.raises(ValueError):
            ServeEngine(
                *gpt2, EngineConfig(**ECFG), spec=spec,
                prefix_store=InProcPrefixStore(),
            )

    def test_fleet_prefix_once(self, gpt2):
        """The headline registry contract: one shared system prompt is
        prefilled by ONE engine; a peer ADOPTS the published pages
        (puts stay at the shared page count) and tokens never change."""
        store = InProcPrefixStore()
        shared = np.arange(1, 17, dtype=np.int32)  # 2 full pages @ ps=8
        rng = np.random.default_rng(5)
        reqs = [
            Request(
                np.concatenate([
                    shared, rng.integers(1, 97, size=5).astype(np.int32)
                ]),
                max_new_tokens=6, request_id=f"shared-{i}",
            )
            for i in range(4)
        ]
        want = _solo_streams(*gpt2, reqs)
        engines = [
            ServeEngine(
                *gpt2,
                # Explicit page_size: the auto default picks 32 at
                # max_len=96, leaving the 16-token prefix with ZERO
                # full pages and nothing to publish.
                EngineConfig(
                    role="solo", engine_id=f"e{i}", page_size=8, **ECFG
                ),
                prefix_store=store,
            )
            for i in range(2)
        ]
        # e0 serves the first two requests and publishes the shared
        # pages...
        h0 = [engines[0].submit(r) for r in reqs[:2]]
        engines[0].run_until_drained()
        assert store.stats()["puts"] == 2  # once per FLEET, exactly
        assert engines[0].store_published_pages == 2
        # ...then e1 must adopt them instead of recomputing: its first
        # shared request splices from the store, the second shares the
        # adopted pages through the normal LOCAL registry
        h1 = [engines[1].submit(r) for r in reqs[2:]]
        engines[1].run_until_drained()
        assert engines[1].store_adopted_pages == 2
        assert engines[1].store_published_pages == 0  # never re-put
        assert store.stats()["puts"] == 2  # STILL once per fleet
        assert store.stats()["hits"] >= 2
        for r, h in zip(reqs, h0 + h1):
            assert h.status is RequestStatus.COMPLETED
            assert h.tokens == want[r.request_id], r.request_id
        # churn: the router's loss hook releases e1's pins; entries
        # stay resident (canonical for the fleet) but become evictable
        store.release_holder("e1")
        assert len(store) == 2


# -- router ----------------------------------------------------------------
class _FakeTelemetry:
    def __init__(self):
        self.engine_id = None
        self.writer = None


class _FakeEngine:
    def __init__(self, role="solo", engine_id=None, sig="sig"):
        self.role = role
        self.engine_id = engine_id
        self.migration_signature = sig
        self.telemetry = _FakeTelemetry()
        self._store = None


class TestRouterConstruction:
    def test_engines_xor_tiers(self):
        with pytest.raises(ValueError, match="not both"):
            Router(
                engines=[_FakeEngine()],
                prefill=[_FakeEngine("prefill")],
                decode=[_FakeEngine("decode")],
            )

    def test_tier_needs_both_sides(self):
        with pytest.raises(ValueError, match="BOTH"):
            Router(prefill=[_FakeEngine("prefill")], decode=[])

    def test_role_mismatch_refused(self):
        with pytest.raises(ValueError, match="role"):
            Router(engines=[_FakeEngine(role="prefill")])

    def test_duplicate_ids_refused(self):
        with pytest.raises(ValueError, match="duplicate"):
            Router(engines=[
                _FakeEngine(engine_id="e0"), _FakeEngine(engine_id="e0"),
            ])

    def test_mixed_geometry_refused(self):
        with pytest.raises(ValueError, match="mixed-geometry"):
            Router(engines=[
                _FakeEngine(sig="a"), _FakeEngine(sig="b"),
            ])

    def test_ids_assigned_and_telemetry_teed(self):
        a, b = _FakeEngine(), _FakeEngine()
        r = Router(engines=[a, b])
        assert [a.engine_id, b.engine_id] == ["e0", "e1"]
        assert a.telemetry.writer is not None
        assert a.telemetry.writer.board is r.board


class TestGaugeBoard:
    def test_rank_total_order(self):
        b = GaugeBoard()
        b.note_routed("e0")
        # fewer outstanding wins; equal load tiebreaks on the id
        assert min(["e0", "e1"], key=b.rank) == "e1"
        b.note_routed("e1")
        assert min(["e0", "e1"], key=b.rank) == "e0"

    def test_request_records_decrement(self):
        b = GaugeBoard(ema=0.5)
        b.note_routed("e0")
        b.ingest("e0", {"event": "request", "ttft_ms": 10.0})
        st = b.snapshot()["e0"]
        assert st["outstanding"] == 0
        assert st["ttft_ewma_ms"] == 10.0  # first sample seeds the EWMA
        b.note_routed("e0")
        b.ingest("e0", {"event": "request", "ttft_ms": 20.0})
        assert b.snapshot()["e0"]["ttft_ewma_ms"] == 15.0

    def test_snapshot_occupancy(self):
        b = GaugeBoard()
        b.ingest("e0", {"event": "snapshot", "slot_occupancy": 0.75})
        assert b.snapshot()["e0"]["slot_occupancy"] == 0.75


class TestRouterFleet:
    def _fleet(self, gpt2, n=2):
        return [
            ServeEngine(
                *gpt2,
                EngineConfig(role="solo", engine_id=f"e{i}", **ECFG),
            )
            for i in range(n)
        ]

    def test_solo_fleet_storm_parity(self, gpt2):
        reqs = _requests(10, seed=51)
        want = _solo_streams(*gpt2, reqs)
        router = Router(engines=self._fleet(gpt2))
        hs = [router.submit(r) for r in reqs]
        router.run_until_drained()
        for r, h in zip(reqs, hs):
            assert h.status is RequestStatus.COMPLETED
            assert h.tokens == want[r.request_id], r.request_id
        s = router.summary()
        assert s["replays"] == 0 and not s["lost_engines"]
        assert sum(
            e.get("completed", 0) for e in s["engines"].values()
        ) == len(reqs)

    def test_disagg_fleet_storm_parity(self, gpt2):
        """1 prefill + 1 decode through the router's outbox drain: every
        stream matches solo, and the migration accounting is exact."""
        reqs = _requests(8, seed=52)
        want = _solo_streams(*gpt2, reqs)
        pre = ServeEngine(
            *gpt2, EngineConfig(role="prefill", engine_id="p0", **ECFG)
        )
        dec = ServeEngine(
            *gpt2, EngineConfig(role="decode", engine_id="d0", **ECFG)
        )
        router = Router(prefill=[pre], decode=[dec])
        hs = [router.submit(r) for r in reqs]
        router.run_until_drained()
        for r, h in zip(reqs, hs):
            assert h.status is RequestStatus.COMPLETED
            assert h.tokens == want[r.request_id], r.request_id
        assert router.migration_frames == len(reqs)
        per_page = frame_nbytes(pre.pool.cache)
        ps = pre.pool.page_size
        pages = sum(-(-r.prompt_len // ps) for r in reqs)
        # EXACT payload accounting: every migrated page, nothing else
        assert router.migration_payload_bytes == pages * per_page
        assert router.migration_bytes > router.migration_payload_bytes

    def test_engine_loss_replay_parity(self, gpt2):
        """Evict-and-replay: kill e1 mid-storm; its in-flight requests
        replay on the survivor and every FINAL stream matches the
        no-fault run bit for bit."""
        reqs = _requests(10, seed=53)
        want = _solo_streams(*gpt2, reqs)
        router = Router(engines=self._fleet(gpt2))
        hs = [router.submit(r) for r in reqs]
        with faults.injected("serve.engine_loss:mode=raise,match=e1,after=2"):
            router.run_until_drained()
        assert router.lost_engines == ["e1"]
        assert router.replays >= 1
        for r, h in zip(reqs, hs):
            assert h.status is RequestStatus.COMPLETED, (
                r.request_id, h.status,
            )
            assert h.tokens == want[r.request_id], r.request_id
        # every replayed handle landed on the survivor
        assert all(
            h.engine_id == "e0" for h in hs if h.replays
        )

    def test_losing_the_last_tier_member_is_loud(self, gpt2):
        reqs = _requests(4, seed=54)
        router = Router(engines=self._fleet(gpt2, n=1))
        for r in reqs:
            router.submit(r)
        with faults.injected("serve.engine_loss:mode=raise,match=e0"):
            with pytest.raises(RuntimeError, match="surviving"):
                router.run_until_drained()

    def test_drive_duck_compat(self, gpt2):
        from pytorch_distributed_tpu.serve import drive, uniform_arrivals

        reqs = _requests(6, seed=55)
        want = _solo_streams(*gpt2, reqs)
        router = Router(engines=self._fleet(gpt2))
        wall = drive(router, reqs, uniform_arrivals(len(reqs), 0.0))
        assert wall > 0
        for r in reqs:
            rh = router._live[r.request_id]
            assert rh.tokens == want[r.request_id]

    def test_router_records_migrations(self, gpt2):
        from pytorch_distributed_tpu.train.metrics import (
            MetricsWriter,
            read_metrics,
        )

        reqs = _requests(3, seed=56)
        import tempfile

        with tempfile.TemporaryDirectory() as d:
            path = f"{d}/m.jsonl"
            writer = MetricsWriter(path)
            pre = ServeEngine(
                *gpt2,
                EngineConfig(role="prefill", engine_id="p0", **ECFG),
            )
            dec = ServeEngine(
                *gpt2,
                EngineConfig(role="decode", engine_id="d0", **ECFG),
            )
            router = Router(
                prefill=[pre], decode=[dec], writer=writer
            )
            for r in reqs:
                router.submit(r)
            router.run_until_drained()
            writer.close()
            recs = [
                m for m in read_metrics(path)
                if m.get("split") == "serve"
                and m.get("event") == "migrate"
            ]
        assert len(recs) == len(reqs)
        assert all(r["engine_id"] == "p0" and r["dst"] == "d0"
                   for r in recs)
        assert sum(int(r["payload_nbytes"]) for r in recs) == (
            router.migration_payload_bytes
        )


# -- multi-process ---------------------------------------------------------
def test_migration_over_ring():
    """The same hand-off over the ring's REAL P2P mailboxes, int8
    payloads included — 2 spawned processes, parity pinned receiver-side."""
    world = 2
    results = hostring_workers.run_ring_workers(
        world, hostring_workers.disagg_migration_worker, timeout=420.0
    )
    assert results == [(r, "ok") for r in range(world)], results


@pytest.mark.slow
def test_storm_with_loss_drill(gpt2):
    """The big drill: 2 prefill + 2 decode under a 32-request storm with
    a decode engine killed mid-flight — every stream still matches the
    solo reference, and the fleet's accounting stays exact."""
    from pytorch_distributed_tpu.serve import prefix_shared_requests

    rng = np.random.default_rng(9)
    reqs = prefix_shared_requests(
        rng, 32, 97, prompt_len=(4, 24), new_tokens=(4, 12),
        prefix_share=0.5, shared_prefix_len=8,
    )
    want = _solo_streams(*gpt2, reqs)

    def fleet():
        pre = [
            ServeEngine(
                *gpt2,
                EngineConfig(role="prefill", engine_id=f"p{i}", **ECFG),
            )
            for i in range(2)
        ]
        dec = [
            ServeEngine(
                *gpt2,
                EngineConfig(role="decode", engine_id=f"d{i}", **ECFG),
            )
            for i in range(2)
        ]
        return Router(prefill=pre, decode=dec)

    router = fleet()
    hs = [router.submit(r) for r in reqs]
    with faults.injected("serve.engine_loss:mode=raise,match=d1,after=4"):
        router.run_until_drained()
    assert router.lost_engines == ["d1"]
    for r, h in zip(reqs, hs):
        assert h.status is RequestStatus.COMPLETED, (r.request_id, h.status)
        assert h.tokens == want[r.request_id], r.request_id
    s = router.summary()
    assert s["migration_frames"] >= len(reqs)  # replays re-migrate
    assert "ttft_ms_p99" in s
