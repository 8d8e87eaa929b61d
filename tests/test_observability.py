"""Observability subsystem (runtime/tracing.py + the unified timers).

The contracts under test: spans nest and order correctly in a
Perfetto-loadable trace.json; the recompile sentinel fires on a
steady-state recompile and stays silent on a steady loop; goodput
buckets always sum to wall time (including under injected faults); the
disarmed path is a single is-None test returning one shared no-op
object; the torn-final-line chaos scenario no longer breaks
``read_metrics``; and ScalarMeter/StepTimer/ServeTelemetry all report
percentiles through the one shared helper.
"""

import contextlib
import json
import logging
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorch_distributed_tpu.data import ArrayDataset, DataLoader
from pytorch_distributed_tpu.parallel import DataParallel
from pytorch_distributed_tpu.runtime import faults, tracing
from pytorch_distributed_tpu.runtime.compat import (
    jit_cache_size,
    live_buffer_bytes,
)
from pytorch_distributed_tpu.runtime.mesh import MeshSpec, make_mesh
from pytorch_distributed_tpu.train import (
    Trainer,
    TrainerConfig,
    TrainState,
    build_train_step,
)
from pytorch_distributed_tpu.train.metrics import (
    MeterState,
    MetricsWriter,
    ScalarMeter,
    read_metrics,
)
from pytorch_distributed_tpu.utils.profiler import StepTimer
from pytorch_distributed_tpu.utils.timing import WindowTimer, percentile

pytestmark = pytest.mark.obs


@contextlib.contextmanager
def ptd_caplog(caplog, level="WARNING"):
    """Route the repo's namespace logger (propagate=False, own handler)
    into caplog, which only listens on the root logger."""
    ns = logging.getLogger("pytorch_distributed_tpu")
    ns.addHandler(caplog.handler)
    try:
        with caplog.at_level(level, logger="pytorch_distributed_tpu"):
            yield caplog
    finally:
        ns.removeHandler(caplog.handler)


# -- the disarmed path -----------------------------------------------------
class TestDisarmed:
    def test_disabled_span_is_one_shared_noop(self):
        tracing.clear()
        assert not tracing.active()
        s1 = tracing.span("train.step")
        s2 = tracing.span("serve.decode_tick", active=3)
        # the faults.py discipline: a single module-global is-None test,
        # then ONE shared object — no allocation per site
        assert s1 is s2 is tracing._NULL_SPAN
        with s1:
            pass  # reentrant, no-op
        assert tracing.instant("x", a=1) is None
        assert tracing.counter("x", 1.0) is None
        assert tracing.note_compiles("x", 5) is None

    def test_disabled_sites_are_cheap(self):
        tracing.clear()
        t0 = time.perf_counter()
        for _ in range(100_000):
            with tracing.span("hot"):
                pass
        dt = time.perf_counter() - t0
        # generous bound (contended 1-core box): the point is "no clock
        # read, no dict, no allocation per call", not a microbenchmark
        assert dt < 1.0, f"{dt:.3f}s for 100k disarmed spans"


# -- recording -------------------------------------------------------------
class TestSpans:
    def test_nesting_and_ordering(self):
        with tracing.enabled() as t:
            with tracing.span("outer", phase="a"):
                time.sleep(0.002)
                with tracing.span("inner"):
                    time.sleep(0.002)
                time.sleep(0.002)
        ev = {e["name"]: e for e in t._events}
        inner, outer = ev["inner"], ev["outer"]
        # inner completes first, so it lands in the buffer first
        assert [e["name"] for e in t._events] == ["inner", "outer"]
        # and its interval is contained in outer's
        assert outer["ts"] <= inner["ts"]
        assert (inner["ts"] + inner["dur"]) <= (outer["ts"] + outer["dur"])
        # the caller's keys (an armed span adds span_id / parent_id)
        assert outer["args"]["phase"] == "a"
        assert inner["args"]["parent_id"] == outer["args"]["span_id"]
        assert inner["tid"] == outer["tid"]

    def test_trace_json_schema(self, tmp_path):
        with tracing.enabled(str(tmp_path)) as t:
            with tracing.span("a", k=1):
                pass
            tracing.instant("marker", why="test")
            tracing.counter("gauge", 42.0)
            path = t.export()
        assert path == str(tmp_path / "trace.json")
        doc = json.load(open(path))
        assert isinstance(doc["traceEvents"], list)
        assert doc["displayTimeUnit"] == "ms"
        assert doc["otherData"]["dropped_events"] == 0
        phs = sorted(e["ph"] for e in doc["traceEvents"])
        assert phs == ["C", "X", "i"]
        for e in doc["traceEvents"]:
            for key in ("name", "ph", "ts", "pid", "tid"):
                assert key in e, e
            if e["ph"] == "X":
                assert e["dur"] >= 0

    def test_buffer_cap_drops_loudly_but_rollups_keep_counting(self):
        with tracing.enabled(max_events=10) as t:
            for _ in range(25):
                with tracing.span("spin"):
                    pass
        assert len(t._events) == 10
        assert t.dropped == 15
        assert t.rollups()["spin"]["count"] == 25  # aggregates uncapped

    def test_rollup_memory_bounded_but_aggregates_exact(self):
        """A run longer than sample_cap keeps exact count/total/max
        (scalars) while the percentile sample stays bounded."""
        t = tracing.Tracer(max_events=10, sample_cap=8)
        durs = [0.001 * i for i in range(1, 21)]
        for d in durs:
            t.complete("x", None, 0.0, d)
        assert len(t._samples["x"]) == 8  # bounded (the newest 8)
        roll = t.rollups()["x"]
        assert roll["count"] == 20
        assert roll["total_ms"] == pytest.approx(sum(durs) * 1e3)
        assert roll["max_ms"] == pytest.approx(max(durs) * 1e3)
        # percentiles come from the retained window
        assert roll["p50_ms"] == pytest.approx(
            percentile(durs[-8:], 50) * 1e3
        )

    def test_rollup_percentiles_match_shared_helper(self):
        t = tracing.Tracer()
        durs = [0.001 * i for i in range(1, 21)]
        for d in durs:
            t.complete("x", None, 0.0, d)
        roll = t.rollups()["x"]
        assert roll["count"] == 20
        assert roll["p95_ms"] == pytest.approx(percentile(durs, 95) * 1e3)
        assert roll["p50_ms"] == pytest.approx(percentile(durs, 50) * 1e3)
        assert roll["max_ms"] == pytest.approx(max(durs) * 1e3)

    def test_write_rollups_speaks_metrics_protocol(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        t = tracing.Tracer()
        t.complete("a", None, 0.0, 0.5)
        t.note_compiles("f", 1)
        t.note_compiles("f", 3)  # 2 recompiles after warm-up
        with MetricsWriter(path) as w:
            t.write_rollups(w, step=7)
        recs = read_metrics(path)
        spans = [r for r in recs if r.get("event") == "span_rollup"]
        assert [r["span"] for r in spans] == ["a"]
        assert all(r["split"] == "trace" for r in recs)
        rc = [r for r in recs if r.get("event") == "recompiles"]
        assert rc[0]["recompiles_total"] == 2
        assert rc[0]["recompiles.f"] == 2
        assert rc[0]["compiles.f"] == 3  # cumulative, warm-up included


# -- recompile sentinel ----------------------------------------------------
class TestRecompileSentinel:
    def test_fires_on_shape_change_silent_on_steady_loop(self, caplog):
        f = jax.jit(lambda x: x * 2.0)
        with tracing.enabled() as t:
            f(jnp.ones(4))
            n = jit_cache_size(f)
            assert n is not None and n >= 1  # the poll works on this jax
            tracing.note_compiles("f", n)  # warm-up baseline
            with ptd_caplog(caplog):
                for _ in range(5):  # steady loop: same shape, no firing
                    f(jnp.ones(4))
                    tracing.note_compiles("f", jit_cache_size(f))
                assert t.recompiles == {}
                assert not any(
                    "RECOMPILE" in r.message for r in caplog.records
                )
                f(jnp.ones(5))  # the classic silent regression
                tracing.note_compiles("f", jit_cache_size(f))
            assert t.recompiles == {"f": 1}
            assert any("RECOMPILE" in r.message for r in caplog.records)
            # and it is marked on the timeline
            marks = [e for e in t._events if e["name"] == "recompile"]
            assert marks and marks[0]["args"]["callable"] == "f"

    def test_serve_engine_counters_wired(self):
        """A steady serve workload reports its compile counters through
        the sentinel (baseline only — no recompile), and the engine tick
        lands serve.* spans on the timeline."""
        from pytorch_distributed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
        from pytorch_distributed_tpu.serve import (
            EngineConfig,
            Request,
            ServeEngine,
        )

        cfg = GPT2Config(
            vocab_size=61, n_positions=32, hidden_size=16, num_layers=1,
            num_heads=2, dropout_rate=0.0,
        )
        model = GPT2LMHead(cfg)
        params = model.init(
            jax.random.key(0), jnp.zeros((1, 4), jnp.int32)
        )["params"]
        rng = np.random.default_rng(0)
        with tracing.enabled() as t:
            engine = ServeEngine(model, params, EngineConfig(
                num_slots=2, max_len=16, prefill_chunk=4,
            ))
            for _ in range(3):
                engine.submit(Request(
                    rng.integers(1, 61, size=5).astype(np.int32),
                    max_new_tokens=4,
                ))
            engine.run_until_drained()
            names = {e["name"] for e in t._events}
            assert {"serve.prefill_chunk", "serve.decode_tick",
                    "serve.token_fetch", "serve.admit",
                    "serve.evict"} <= names
            # one compile per program (the engine invariant) -> baseline
            # recorded, zero recompiles
            assert t._compiles["serve.decode"] == 1
            assert t._compiles["serve.prefill"] == 1
            assert t.recompiles == {}


# -- goodput ---------------------------------------------------------------
class TestGoodput:
    def test_buckets_sum_to_wall_fake_clock(self):
        now = [100.0]
        g = tracing.GoodputAccount(clock=lambda: now[0])
        now[0] += 10.0
        g.add("productive", 6.0)
        g.add("recovering", 1.5)
        g.add("stalled", 0.5)
        s = g.summary()
        total = sum(
            v for k, v in s.items()
            if k.endswith("_s") and k != "wall_s"
        )
        assert total == pytest.approx(s["wall_s"])
        assert s["goodput_pct"] == pytest.approx(60.0)
        assert s["other_s"] == pytest.approx(2.0)

    def test_resize_bucket_reported_and_sums_to_wall(self):
        """The elastic-world bucket (r13): ``resize`` is a first-class
        goodput bucket — always present in the summary (0.0 when no
        resize happened), and the sum-to-wall invariant holds with it
        charged."""
        assert "resize" in tracing.GOODPUT_BUCKETS
        now = [0.0]
        g = tracing.GoodputAccount(clock=lambda: now[0])
        now[0] += 20.0
        g.add("productive", 12.0)
        g.add("resize", 3.0)
        g.add("recovering", 2.0)
        g.add("checkpoint", 1.0)
        s = g.summary()
        assert s["resize_s"] == pytest.approx(3.0)
        total = sum(
            v for k, v in s.items()
            if k.endswith("_s") and k != "wall_s"
        )
        assert total == pytest.approx(s["wall_s"])
        assert s["other_s"] == pytest.approx(2.0)
        # an account that never resized still REPORTS the bucket: a
        # dashboard diffing runs must not see a schema change
        empty = tracing.GoodputAccount(clock=lambda: now[0]).summary()
        assert empty["resize_s"] == 0.0

    def test_rebalance_bucket_reported_and_sums_to_wall(self):
        """The heterogeneity-balancer bucket (r15): ``rebalance`` is a
        first-class goodput bucket — the rate-allgather + assignment
        derivation at each boundary is priced separately, so the bench
        ``hetero`` phase's balancing win is net of what the balancer
        itself costs — and the sum-to-wall invariant holds with it
        charged."""
        assert "rebalance" in tracing.GOODPUT_BUCKETS
        now = [0.0]
        g = tracing.GoodputAccount(clock=lambda: now[0])
        now[0] += 10.0
        g.add("productive", 7.0)
        g.add("rebalance", 0.5)
        g.add("resize", 1.5)
        s = g.summary()
        assert s["rebalance_s"] == pytest.approx(0.5)
        total = sum(
            v for k, v in s.items()
            if k.endswith("_s") and k != "wall_s"
        )
        assert total == pytest.approx(s["wall_s"])
        assert s["other_s"] == pytest.approx(1.0)
        # never-rebalanced accounts still report the bucket (schema)
        empty = tracing.GoodputAccount(clock=lambda: now[0]).summary()
        assert empty["rebalance_s"] == 0.0
        # ...and summarize_goodput carries it through the JSONL account
        summ = tracing.summarize_goodput(
            [{"split": "goodput", "rebalance_s": 0.25, "wall_s": 1.0,
              "productive_s": 0.75}]
        )
        assert summ["rebalance_s"] == pytest.approx(0.25)

    def test_buckets_sum_to_wall_under_injected_faults(self, tmp_path):
        """End to end: a Trainer run with PTD_FAULTS armed (a step.nan
        injection plus a checkpoint cadence) still accounts every wall
        second into a bucket."""
        make_mesh(MeshSpec(dp=8))
        dp = DataParallel()

        def loss_fn(params, batch_stats, batch, rng):
            loss = jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)
            return loss, {"metrics": {"loss": loss},
                          "batch_stats": batch_stats}

        state = TrainState.create(
            apply_fn=lambda p, x: x @ p["w"],
            params={"w": jnp.ones((4, 2))}, tx=optax.sgd(0.05),
        )
        rng = np.random.default_rng(0)
        ds = ArrayDataset(
            x=rng.normal(size=(64, 4)).astype(np.float32),
            y=rng.normal(size=(64, 2)).astype(np.float32),
        )
        metrics_path = str(tmp_path / "m.jsonl")
        trainer = Trainer(
            state, dp, build_train_step(loss_fn),
            DataLoader(ds, 16, sharding=dp.batch_sharding()),
            config=TrainerConfig(
                epochs=2, log_every=1, metrics_path=metrics_path,
                ckpt_dir=str(tmp_path / "ckpt"), ckpt_every_steps=3,
                halt_on_nonfinite=0,  # survive the injected NaN
            ),
        )
        with faults.injected("step.nan:mode=raise,count=1"):
            trainer.fit()
        recs = read_metrics(metrics_path)
        g = [r for r in recs if r["split"] == "goodput"]
        assert len(g) == 1
        s = g[0]
        total = sum(
            v for k, v in s.items()
            if isinstance(v, float) and k.endswith("_s") and k != "wall_s"
        )
        assert total == pytest.approx(s["wall_s"], rel=0.02)
        assert s["productive_s"] > 0
        assert s["checkpoint_s"] > 0  # the ckpt cadence was attributed
        # every train log record carries the running goodput_pct
        train_recs = [r for r in recs if r["split"] == "train"]
        assert train_recs and all("goodput_pct" in r for r in train_recs)

    def test_retract_reclassifies_resolved_stall(self):
        """A watchdog stall that resolves inside an attributed section
        (a slow-but-progressing op) must not be double-billed: the
        section's bucket covers its wall, the stalled seconds retract,
        and the buckets keep summing to wall."""
        now = [0.0]
        g = tracing.GoodputAccount(clock=lambda: now[0])
        now[0] += 10.0
        g.add("stalled", 3.0)  # watchdog fired mid-fetch...
        g.add("productive", 9.0)  # ...but the fetch returned
        g.retract("stalled", 3.0)
        s = g.summary()
        assert s["stalled_s"] == 0.0
        assert s["productive_s"] == 9.0
        total = sum(
            v for k, v in s.items()
            if k.endswith("_s") and k != "wall_s"
        )
        assert total == pytest.approx(s["wall_s"])
        g.retract("stalled", 99.0)  # clamped at balance, never negative
        assert g.buckets["stalled"] == 0.0

    def test_summarize_goodput_across_attempts(self):
        recs = [
            {"split": "goodput", "wall_s": 10.0, "productive_s": 6.0,
             "recovering_s": 1.0},
            {"split": "goodput", "wall_s": 5.0, "productive_s": 4.0,
             "checkpoint_s": 0.5},
            {"split": "train", "loss": 1.0},
        ]
        g = tracing.summarize_goodput(recs)
        assert g["attempts_recorded"] == 2
        assert g["productive_s"] == pytest.approx(10.0)
        assert g["goodput_pct"] == pytest.approx(100 * 10.0 / 15.0, abs=0.01)
        # a drill passes its own wall (restart gaps included)
        g2 = tracing.summarize_goodput(recs, wall_s=20.0)
        assert g2["goodput_pct"] == pytest.approx(50.0)
        assert g2["wall_s"] == 20.0


# -- the one-flag trainer path --------------------------------------------
class TestTrainerTraceFlag:
    def test_trace_flag_produces_timeline_and_rollups(self, tmp_path):
        make_mesh(MeshSpec(dp=8))
        dp = DataParallel()

        def loss_fn(params, batch_stats, batch, rng):
            loss = jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)
            return loss, {"metrics": {"loss": loss},
                          "batch_stats": batch_stats}

        state = TrainState.create(
            apply_fn=lambda p, x: x @ p["w"],
            params={"w": jnp.ones((4, 2))}, tx=optax.sgd(0.05),
        )
        rng = np.random.default_rng(0)
        ds = ArrayDataset(
            x=rng.normal(size=(64, 4)).astype(np.float32),
            y=rng.normal(size=(64, 2)).astype(np.float32),
        )
        metrics_path = str(tmp_path / "m.jsonl")
        trainer = Trainer(
            state, dp, build_train_step(loss_fn),
            DataLoader(ds, 16, sharding=dp.batch_sharding()),
            config=TrainerConfig(
                epochs=1, log_every=2, metrics_path=metrics_path,
                ckpt_dir=str(tmp_path / "ckpt"),
                trace=str(tmp_path),
            ),
        )
        # armed at CONSTRUCTION, not fit(): every recipe restores before
        # fitting, and the train.restore span must land on the timeline
        assert tracing.active()
        trainer.restore_checkpoint()  # nothing on disk — span still lands
        trainer.fit()
        assert not tracing.active()  # fit() disarms its own tracer
        doc = json.load(open(tmp_path / "trace.json"))
        names = {e["name"] for e in doc["traceEvents"]}
        # trainer spans AND ingest spans (producer thread) on one timeline
        assert {"train.step", "train.data_wait", "train.metric_fetch",
                "train.checkpoint", "train.restore", "ingest.fetch",
                "ingest.place"} <= names
        # ingest spans really ride the producer thread's own track
        tids = {
            e["name"]: e["tid"] for e in doc["traceEvents"]
            if e["ph"] == "X"
        }
        assert tids["ingest.fetch"] != tids["train.step"]
        # rollups + device memory gauge landed in the metrics stream
        recs = read_metrics(metrics_path)
        spans = {
            r["span"] for r in recs if r.get("event") == "span_rollup"
        }
        assert "train.step" in spans and "ingest.fetch" in spans
        train_recs = [r for r in recs if r["split"] == "train"]
        assert any("device_bytes_in_use" in r for r in train_recs)

    def test_obs_report_renders_run_dir(self, tmp_path, capsys):
        """scripts/obs_report.py turns the flag's output into the
        breakdown + goodput report."""
        with tracing.enabled(str(tmp_path)) as t:
            with tracing.span("train.step"):
                time.sleep(0.001)
            t.note_compiles("train.step", 1)
            t.note_compiles("train.step", 2)
            t.export()
        with MetricsWriter(str(tmp_path / "m.jsonl")) as w:
            g = tracing.GoodputAccount()
            g.add("productive", 0.5)
            w.write(1, {"event": "goodput", **g.summary()},
                    split="goodput")
            # two attempts' recompile records SUM (each fit() has a
            # fresh tracer); trace.json duplicates the last attempt's
            # count (1) and must merge by max, not add
            w.write(1, {"event": "recompiles", "recompiles_total": 2,
                        "recompiles.train.step": 2}, split="trace")
            w.write(2, {"event": "recompiles", "recompiles_total": 1,
                        "recompiles.train.step": 1}, split="trace")
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts",
        ))
        try:
            import obs_report
        finally:
            sys.path.pop(0)
        rc = obs_report.main([str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "Step-phase breakdown" in out
        assert "train.step" in out
        assert "INVESTIGATE" in out  # the recompile was surfaced
        # summed across attempt records (2+1), trace's 1 merged by max
        assert "train.step: 3 steady-state" in out
        assert "Goodput" in out

    def test_obs_report_stragglers_section(self, tmp_path, capsys):
        """r15: the Stragglers section renders all three inputs — the
        per-rank step skew from a merged trace (pid = rank after
        trace_merge), the ``train.rank_skew`` gauge the rebalancer
        emits, and the ``split="elastic"`` rebalance audit records —
        and a run with none of them prints no section at all."""
        # a merged-trace shape: rank 1's steps take 2x rank 0's
        events = []
        for rank, dur_us in ((0, 10_000.0), (1, 20_000.0)):
            for k in range(3):
                events.append({
                    "name": "elastic.step", "ph": "X", "pid": rank,
                    "tid": 0, "ts": k * 30_000.0, "dur": dur_us,
                })
        events.append({
            "name": "train.rank_skew", "ph": "C", "pid": 0, "tid": 0,
            "ts": 0.0, "args": {"value": 2.0},
        })
        (tmp_path / "trace.json").write_text(json.dumps(
            {"traceEvents": events, "otherData": {}}
        ))
        with MetricsWriter(str(tmp_path / "m.jsonl")) as w:
            w.write(8, {"event": "rebalance", "reason": "interval",
                        "counts": [8, 4], "skew": 2.0,
                        "changed": True}, split="elastic")
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts",
        ))
        try:
            import obs_report
        finally:
            sys.path.pop(0)
        rc = obs_report.main([str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "Stragglers" in out
        assert "step-time skew (slowest/fastest rank): 2.00x" in out
        assert "train.rank_skew gauge: last 2.00x" in out
        assert "counts=[8, 4]" in out and "moved" in out
        # silent when a run carries none of the three inputs
        solo = tmp_path / "solo"
        solo.mkdir()
        with MetricsWriter(str(solo / "m.jsonl")) as w:
            w.write(1, {"loss": 1.0}, split="train")
        assert obs_report.main([str(solo)]) == 0
        assert "Stragglers" not in capsys.readouterr().out

    def test_obs_report_fleet_section(self, tmp_path, capsys):
        """r18: the Fleet section renders per-engine request/TTFT/
        occupancy lines from engine_id-labeled serve records plus the
        router's migrate/replay audit — and a single-engine run (no
        engine_id label, no router records) keeps the old Serving
        section and prints no Fleet section at all."""
        with MetricsWriter(str(tmp_path / "m.jsonl")) as w:
            for eid, ttft in (("d0", 40.0), ("d0", 60.0), ("d1", 90.0)):
                w.write(1, {"event": "request", "engine_id": eid,
                            "request_id": "r", "status": "completed",
                            "prompt_tokens": 8, "new_tokens": 4,
                            "ttft_ms": ttft}, split="serve")
            w.write(2, {"event": "snapshot", "engine_id": "d0",
                        "queue_depth": 0, "slots_occupied": 2,
                        "slots_total": 4, "slot_occupancy": 0.5,
                        "decode_ticks": 9}, split="serve")
            w.write(3, {"event": "migrate", "engine_id": "p0",
                        "dst": "d0", "request_id": "r", "nbytes": 2000,
                        "payload_nbytes": 1280, "n_pages": 1},
                    split="serve")
            w.write(4, {"event": "replay", "engine_id": "d1",
                        "dst": "d0", "request_id": "r"}, split="serve")
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts",
        ))
        try:
            import obs_report
        finally:
            sys.path.pop(0)
        rc = obs_report.main([str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "Fleet" in out
        assert "2 engine(s)" in out
        assert "d0" in out and "d1" in out
        assert "2 completed" in out  # d0's two requests grouped
        assert "occupancy last 0.50" in out
        assert "1 frame(s), 1 page(s)" in out
        assert "re-admitted after losing d1" in out
        # single-engine runs (no engine_id label) stay Serving-only
        solo = tmp_path / "solo"
        solo.mkdir()
        with MetricsWriter(str(solo / "m.jsonl")) as w:
            w.write(1, {"event": "request", "request_id": "r",
                        "status": "completed", "prompt_tokens": 8,
                        "new_tokens": 4, "ttft_ms": 12.0},
                    split="serve")
        assert obs_report.main([str(solo)]) == 0
        solo_out = capsys.readouterr().out
        assert "Fleet" not in solo_out
        assert "Serving" in solo_out


# -- torn metrics (the PR 2 chaos scenario) --------------------------------
class TestTornMetrics:
    def test_read_metrics_skips_torn_final_line(self, tmp_path, caplog):
        """A writer SIGKILLed mid-record (os._exit: no flush ordering,
        no atexit) leaves a truncated final line; read_metrics must keep
        every durable record and warn, not raise."""
        path = str(tmp_path / "m.jsonl")
        code = (
            "import json, os\n"
            f"f = open({path!r}, 'w')\n"
            "for i in range(3):\n"
            "    f.write(json.dumps({'step': i, 'split': 'train',"
            " 'loss': 1.0}) + '\\n')\n"
            "f.write('{\"step\": 3, \"split\": \"train\", \"lo')\n"
            "f.flush()\n"
            "os._exit(113)\n"  # the mid-write kill
        )
        proc = subprocess.run([sys.executable, "-c", code])
        assert proc.returncode == 113
        with ptd_caplog(caplog):
            recs = read_metrics(path)
        assert [r["step"] for r in recs] == [0, 1, 2]
        assert any("torn" in r.message for r in caplog.records)
        with pytest.raises(ValueError):
            read_metrics(path, strict=True)

    def test_metrics_writer_context_manager_and_flush(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        with MetricsWriter(path) as w:
            w.write(1, {"loss": 2.0})
            w.flush()
            assert read_metrics(path)[0]["loss"] == 2.0  # durable pre-close
        assert w._f is None  # __exit__ closed it
        w.write(2, {"loss": 1.0})  # reopen-on-reuse contract still holds
        w.close()
        assert len(read_metrics(path)) == 2


# -- unified timers --------------------------------------------------------
class TestUnifiedTimers:
    def test_percentile_matches_numpy_linear(self):
        vals = list(np.random.default_rng(0).normal(size=37))
        for q in (0, 10, 50, 95, 99, 100):
            assert percentile(vals, q) == pytest.approx(
                float(np.percentile(vals, q))
            )
        with pytest.raises(ValueError):
            percentile(vals, 101)

    def test_scalar_meter_and_step_timer_share_window_timer(self):
        assert isinstance(StepTimer(), WindowTimer)
        m = ScalarMeter(window=4)
        assert isinstance(m._timer, WindowTimer)
        for st in (0.1, 0.2, 0.3, 0.4):
            m.update(MeterState(step_time=st, samples_per_sec=10.0 / st))
        s = m.summary()
        assert s["step_time_ms"] == pytest.approx(250.0)
        assert s["step_time_p50_ms"] == pytest.approx(
            percentile([100, 200, 300, 400], 50)
        )
        assert s["step_time_p95_ms"] == pytest.approx(
            percentile([100, 200, 300, 400], 95)
        )
        # StepTimer keeps its historical fraction-q call shape
        t = StepTimer(window=8)
        t.add(1.0)
        t.add(3.0)
        assert t.percentile(0.5) == pytest.approx(percentile([1.0, 3.0], 50))
        assert t.summary()["steps_timed"] == 2

    def test_serve_telemetry_routes_shared_percentile(self):
        from pytorch_distributed_tpu.serve import ServeTelemetry

        tel = ServeTelemetry(clock=lambda: 0.0)
        tel.ttfts_s = [0.010, 0.020, 0.100]
        assert tel.ttft_percentile_ms(50) == pytest.approx(
            percentile([10.0, 20.0, 100.0], 50)
        )
        assert tel.ttft_percentile_ms(99) == pytest.approx(
            percentile([10.0, 20.0, 100.0], 99)
        )
        s = tel.summary()
        assert s["ttft_ms_p50"] == pytest.approx(20.0)


# -- memory gauge ----------------------------------------------------------
def test_live_buffer_bytes_sees_a_big_allocation():
    base = live_buffer_bytes()
    assert base is not None and base >= 0
    big = jnp.ones((1 << 20,), jnp.float32)  # 4 MB, held live
    big.block_until_ready()
    grown = live_buffer_bytes()
    assert grown >= base + 4 * (1 << 20) * 0.9
    del big
