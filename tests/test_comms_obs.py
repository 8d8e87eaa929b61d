"""Comms observability: wire-level collective accounting, cross-rank
trace merge, and the calibrated α–β cost model.

The contracts under test: every HostRingGroup collective records a
``comm.*`` span whose wire bytes follow the NCCL convention EXACTLY
(q8 counts its real int8+scales payload — the ~4x reduction is a
recorded fact); disarmed collectives stay on the shared no-op object;
``scripts/trace_merge.py`` aligns per-rank traces into one Perfetto
timeline with temporally-consistent tracks; the cost model recovers a
synthetic α–β within tolerance and ``collective_bench --fit`` emits a
``costmodel.json`` whose predictions hold within 2x on its own sweep;
coalesced ``sync_grads`` is bit-identical to per-leaf (world 2) with
the span counts proving the collective-count drop; and DETAIL debug
mode now names barrier/P2P divergence instead of hanging.
"""

import json
import os
import subprocess
import sys
import uuid

import numpy as np
import pytest

from pytorch_distributed_tpu.runtime import costmodel, tracing
from pytorch_distributed_tpu.runtime.hostring import (
    Q8_BLOCK,
    _COMM_CUM,
    algo_wire_bytes,
    q8_wire_payload,
)
from tests import hostring_workers

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = os.path.join(REPO, "scripts")


_run = hostring_workers.run_ring_workers  # THE shared spawn harness


# -- wire-byte accounting --------------------------------------------------
class TestWireBytes:
    def test_nccl_convention_factors(self):
        # per-participant algorithmic bytes, the NCCL-tests busbw basis
        assert algo_wire_bytes("all_reduce", 1000, 4) == 1500  # 2(n-1)/n
        assert algo_wire_bytes("all_gather", 1000, 4) == 750  # (n-1)/n
        assert algo_wire_bytes("reduce_scatter", 1000, 4) == 750
        assert algo_wire_bytes("broadcast", 1000, 4) == 1000
        assert algo_wire_bytes("send", 1000, 4) == 1000
        assert algo_wire_bytes("recv", 1000, 4) == 1000
        assert algo_wire_bytes("permute", 1000, 4) == 1000
        assert algo_wire_bytes("barrier", 0, 4) == 0
        # a one-rank world moves nothing, whatever the op
        assert algo_wire_bytes("all_reduce", 1000, 1) == 0
        with pytest.raises(ValueError):
            algo_wire_bytes("gossip", 1000, 4)

    def test_q8_wire_payload_is_the_real_bytes(self):
        # one int8 per element + one f32 scale per 256-element block
        assert Q8_BLOCK == 256
        assert q8_wire_payload(256) == 256 + 4
        assert q8_wire_payload(257) == 257 + 8  # ragged tail block
        n = 6_400_000  # the ROADMAP gradient size
        ratio = q8_wire_payload(n) / (n * 4)
        assert ratio == pytest.approx(0.2539, abs=0.0005)
        # the acceptance bound: ~0.26x f32 at >= 4096-element sizes
        for n in (4096, 65536, 1 << 20):
            assert q8_wire_payload(n) / (n * 4) < 0.26

    def test_disarmed_comm_sites_stay_shared_noop(self):
        from pytorch_distributed_tpu.runtime.hostring import HostRingGroup

        tracing.clear()
        before = dict(_COMM_CUM)
        with HostRingGroup(f"ptdobs_{uuid.uuid4().hex[:8]}", 0, 1) as g:
            g.all_reduce(np.ones(64, np.float32))
            g.barrier()
            g.broadcast(np.ones(4, np.float32))
        # disarmed collectives never touch the cumulative comm tracks
        assert dict(_COMM_CUM) == before
        # and the armed-path builder is unreachable: the site pattern is
        # `tracing._NULL_SPAN if tracing._tracer is None else ...`
        assert tracing._tracer is None
        assert tracing.span("comm.all_reduce") is tracing._NULL_SPAN

    def test_counter_tracks_reset_per_tracer(self):
        """A re-armed tracing window starts its comm.<op> counter
        tracks from zero — not from the previous window's totals."""
        from pytorch_distributed_tpu.runtime.hostring import (
            HostRingGroup,
            reset_comm_counters,
        )

        def last_calls(t):
            vals = [
                e["args"]["value"] for e in t._events
                if e["ph"] == "C" and e["name"] == "comm.all_reduce.calls"
            ]
            return vals[-1] if vals else None

        with HostRingGroup(f"ptdobs_{uuid.uuid4().hex[:8]}", 0, 1) as g:
            with tracing.enabled() as t1:
                g.all_reduce(np.ones(8, np.float32))
                g.all_reduce(np.ones(8, np.float32))
                assert last_calls(t1) == 2
            with tracing.enabled() as t2:  # fresh window, fresh totals
                g.all_reduce(np.ones(8, np.float32))
                assert last_calls(t2) == 1
                reset_comm_counters()  # explicit window reset (bench)
                g.all_reduce(np.ones(8, np.float32))
                assert last_calls(t2) == 1

    def test_comm_spans_multiprocess(self):
        """2-proc ring: every op's span schema + exact wire bytes +
        counter tracks + rollup GB/s + clock-sync metadata."""
        results = _run(2, hostring_workers.comm_span_worker)
        assert results == [(r, "ok") for r in range(2)], results


# -- debug-mode coverage (barrier + P2P) -----------------------------------
class TestDebugFingerprints:
    def test_barrier_mismatch_detected(self):
        results = _run(2, hostring_workers.debug_barrier_mismatch_worker)
        assert results == [(r, "ok") for r in range(2)], results

    def test_p2p_mismatch_detected_both_sides(self):
        results = _run(3, hostring_workers.debug_p2p_worker)
        assert results == [(r, "ok") for r in range(3)], results


# -- cross-rank trace merge ------------------------------------------------
class TestTraceMerge:
    def test_merged_timeline_is_consistent(self, tmp_path):
        world = 3
        results = _run(
            world, hostring_workers.trace_export_worker,
            extra_args=(str(tmp_path),),
        )
        assert results == [(r, "ok") for r in range(world)], results

        sys.path.insert(0, SCRIPTS)
        try:
            import trace_merge
        finally:
            sys.path.pop(0)
        rc = trace_merge.main([str(tmp_path)])
        assert rc == 0
        out = os.path.join(str(tmp_path), "merged_trace.json")
        doc = json.load(open(out))
        events = doc["traceEvents"]
        # one named process track per rank
        names = {
            e["pid"]: e["args"]["name"] for e in events
            if e.get("ph") == "M" and e["name"] == "process_name"
        }
        assert names == {r: f"rank{r}" for r in range(world)}
        assert set(doc["otherData"]["ranks"]) == {
            str(r) for r in range(world)
        }
        # per-rank tracks are monotonically consistent: the k-th
        # collective starts after the (k-1)-th ended
        per_rank = {}
        for e in events:
            if e.get("ph") == "X" and e["name"] == "comm.all_reduce":
                per_rank.setdefault(e["pid"], []).append(e)
        assert set(per_rank) == set(range(world))
        for r, evs in per_rank.items():
            evs.sort(key=lambda e: e["ts"])
            assert len(evs) == 4
            for a, b in zip(evs, evs[1:]):
                assert a["ts"] + a["dur"] <= b["ts"] + 1, (r, a, b)
        # the k-th occurrence is the SAME collective on every rank
        # (barrier lockstep), so the aligned intervals must OVERLAP —
        # the merged-clock consistency claim, not just per-rank order
        tol_us = 2000.0  # barrier-exit jitter bound on this 1-core box
        for k in range(4):
            start = max(per_rank[r][k]["ts"] for r in range(world))
            end = min(
                per_rank[r][k]["ts"] + per_rank[r][k]["dur"]
                for r in range(world)
            )
            assert start <= end + tol_us, (k, start, end)
        # straggler skew was summarized for obs_report (rank r sleeps
        # 2ms x r before issuing, so skew is real and visible)
        skew = doc["otherData"]["comm_skew"]
        assert "comm.all_reduce" in skew
        assert skew["comm.all_reduce"]["ranks"] == world
        assert skew["comm.all_reduce"]["skew_ms_max"] > 0.5

        # obs_report renders the comms section from the merged trace
        sys.path.insert(0, SCRIPTS)
        try:
            import obs_report
        finally:
            sys.path.pop(0)
        import io

        buf = io.StringIO()
        obs_report.report(out, [], out=buf)
        text = buf.getvalue()
        assert "== Comms ==" in text
        assert "comm.all_reduce" in text
        assert "straggler skew" in text

    def test_merge_refuses_duplicate_ranks(self, tmp_path):
        sys.path.insert(0, SCRIPTS)
        try:
            import trace_merge
        finally:
            sys.path.pop(0)
        doc = {"traceEvents": [], "otherData": {"wall_start_unix_s": 1.0,
                                                "meta": {"rank": 0}}}
        a, b = str(tmp_path / "a.json"), str(tmp_path / "b.json")
        for p in (a, b):
            json.dump(doc, open(p, "w"))
        with pytest.raises(ValueError, match="duplicate ranks"):
            trace_merge.merge([a, b])

    def test_merge_refuses_traces_without_wall_anchor(self, tmp_path):
        """A trace with no wall_start_unix_s cannot be clock-aligned;
        defaulting it to 0 would shift real ranks decades apart —
        refuse loudly instead of emitting silent garbage."""
        sys.path.insert(0, SCRIPTS)
        try:
            import trace_merge
        finally:
            sys.path.pop(0)
        good = {"traceEvents": [], "otherData": {
            "wall_start_unix_s": 1.0, "meta": {"rank": 0}}}
        bare = [{"name": "x", "ph": "X", "ts": 1.0, "dur": 1.0,
                 "pid": 1, "tid": 1}]  # bare-array form: no anchor
        a, b = str(tmp_path / "a.json"), str(tmp_path / "bare.json")
        json.dump(good, open(a, "w"))
        json.dump(bare, open(b, "w"))
        with pytest.raises(ValueError, match="wall_start_unix_s"):
            trace_merge.merge([a, b])


# -- cost model ------------------------------------------------------------
class TestCostModel:
    def _synthetic(self, alpha, beta, op="all_reduce", world=4, noise=0.0):
        rng = np.random.default_rng(0)
        records = []
        for payload in (1e4, 1e5, 1e6, 4e6, 1.6e7):
            wire = algo_wire_bytes(op, int(payload), world)
            t = alpha + beta * wire
            records.append({
                "op": op, "payload_bytes": int(payload), "world": world,
                "seconds": t * (1.0 + noise * rng.normal()),
            })
        return records

    def test_fit_recovers_synthetic_alpha_beta(self):
        alpha, beta = 250e-6, 0.8e-9  # 250us latency, 1.25 GB/s
        model = costmodel.fit(
            self._synthetic(alpha, beta, noise=0.02), "test"
        )
        f = model.fits[("all_reduce", 4)]
        assert f.alpha_s == pytest.approx(alpha, rel=0.25)
        assert f.beta_s_per_byte == pytest.approx(beta, rel=0.1)
        assert f.r2 > 0.99
        assert f.bandwidth_gb_s == pytest.approx(1.25, rel=0.1)
        # predictions on the calibration range are tight
        p = model.predict("all_reduce", 1_000_000, 4)
        want = alpha + beta * algo_wire_bytes("all_reduce", 1_000_000, 4)
        assert p.seconds == pytest.approx(want, rel=0.1)
        assert not p.extrapolated
        # the acceptance bar: within 2x across the whole sweep
        worst = costmodel.validate(
            model, self._synthetic(alpha, beta, noise=0.02)
        )
        assert worst["all_reduce"] < 2.0

    def test_predict_flags_extrapolation(self):
        model = costmodel.fit(self._synthetic(1e-4, 1e-9), "test")
        # outside the calibrated size range
        assert model.predict("all_reduce", int(1e9), 4).extrapolated
        # unbenched world: β carries, α scales by barrier phases
        p = model.predict("all_reduce", 1_000_000, 8)
        assert p.extrapolated
        f = model.fits[("all_reduce", 4)]
        want = f.alpha_s * 7 / 3 + f.beta_s_per_byte * algo_wire_bytes(
            "all_reduce", 1_000_000, 8
        )
        assert p.seconds == pytest.approx(want)
        # an op it never saw must refuse, not guess
        with pytest.raises(KeyError):
            model.predict("all_to_all", 1000, 4)

    def test_save_load_roundtrip(self, tmp_path):
        model = costmodel.fit(self._synthetic(1e-4, 1e-9), "spmd:cpu")
        path = model.save(str(tmp_path / "costmodel.json"))
        loaded = costmodel.CostModel.load(path)
        assert loaded.transport == "spmd:cpu"
        assert loaded.fits == model.fits
        doc = json.load(open(path))
        assert doc["format_version"] == costmodel.FORMAT_VERSION
        doc["format_version"] = 99
        with pytest.raises(ValueError, match="format"):
            costmodel.CostModel.from_dict(doc)

    def test_fit_from_metrics_records(self):
        recs = [
            {"split": "comm_bench", "event": "collective", **r,
             "transport": "spmd:cpu"}
            for r in self._synthetic(2e-4, 2e-9)
        ] + [{"split": "train", "loss": 1.0}]  # foreign records ignored
        model = costmodel.fit_from_metrics(recs)
        assert model.transport == "spmd:cpu"
        assert ("all_reduce", 4) in model.fits
        # mixed transports refuse without an explicit pick
        recs.append({"split": "comm_bench", "event": "collective",
                     "op": "all_reduce", "payload_bytes": 1000,
                     "world": 4, "seconds": 1.0,
                     "transport": "hostring"})
        with pytest.raises(ValueError, match="transports"):
            costmodel.fit_from_metrics(recs)
        model = costmodel.fit_from_metrics(recs, transport="spmd:cpu")
        assert model.fits[("all_reduce", 4)].n_samples == 5

    def test_single_size_degenerates_to_pure_bandwidth(self):
        model = costmodel.fit([{
            "op": "all_gather", "payload_bytes": 1_000_000, "world": 2,
            "seconds": 1e-3,
        }], "test")
        f = model.fits[("all_gather", 2)]
        assert f.alpha_s == 0.0
        wire = algo_wire_bytes("all_gather", 1_000_000, 2)
        assert f.beta_s_per_byte == pytest.approx(1e-3 / wire)


# -- collective_bench integration ------------------------------------------
def test_collective_bench_metrics_and_fit(tmp_path):
    """The CLI writes JSONL records and a calibrated costmodel.json
    whose predictions hold within 2x on its own sweep (the acceptance
    bar) — on the virtual 8-device CPU mesh."""
    from pytorch_distributed_tpu.train.metrics import read_metrics

    metrics = str(tmp_path / "comm.jsonl")
    model_path = str(tmp_path / "costmodel.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, "collective_bench.py"),
         "--sizes", "0.02", "0.08", "0.32", "--iters", "5",
         "--metrics-path", metrics, "--fit", model_path],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:] + proc.stdout[-2000:]
    recs = [
        r for r in read_metrics(metrics)
        if r.get("split") == "comm_bench"
    ]
    assert len(recs) == 12, recs  # 4 ops x 3 sizes
    ops = {r["op"] for r in recs}
    assert ops == {"all_reduce", "all_gather", "reduce_scatter",
                   "permute"}
    for r in recs:
        assert r["world"] == 8
        assert r["seconds"] > 0
        assert r["transport"] == "spmd:cpu"
        assert r["wire_bytes"] > 0
    model = costmodel.CostModel.load(model_path)
    assert model.transport == "spmd:cpu"
    assert set(model.ops()) == ops
    # acceptance: predictions within 2x of measured across the sweep
    worst = costmodel.validate(model, recs)
    assert worst and max(worst.values()) < 2.0, worst


# -- coalesced sync_grads --------------------------------------------------
class TestCoalescedSyncGrads:
    def test_bit_identical_and_fewer_collectives(self):
        """world 2: 6 tiny + 1 big leaf -> exactly 2 collectives, flat
        result bit-identical to per-leaf, q8 keeps the flat exact."""
        results = _run(
            2, hostring_workers.coalesce_worker, timeout=300.0
        )
        assert results == [(r, "ok") for r in range(2)], results

    def test_single_controller_is_noop(self):
        """Without a multi-process ring sync_grads stays the identity —
        the coalescing path must not perturb the SPMD case."""
        from pytorch_distributed_tpu.parallel.ddp import sync_grads

        grads = {"a": np.ones(10, np.float32),
                 "b": np.ones(5, np.float32)}
        out = sync_grads(grads)
        assert out is grads
