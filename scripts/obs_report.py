"""Render a run directory into a human-readable observability report.

Input: what one ``--trace-dir`` / ``TrainerConfig.trace`` run leaves
behind — a Chrome-format ``trace.json`` (runtime/tracing.py) and/or any
MetricsWriter JSONL streams (step records with ``goodput_pct``,
``split="trace"`` span rollups, ``split="goodput"`` accounts,
``split="serve"`` telemetry). Output: the tables a slow-step
investigation starts from —

* step-phase breakdown: per-span count / total / mean / p50 / p95 /
  p99 / max and share of traced wall time,
* top-N widest individual spans (the outliers percentiles hide),
* recompile sentinel summary (anything after warm-up is a finding),
* goodput summary (productive / stalled / recovering / checkpoint /
  other seconds; buckets sum to wall),
* comms: per-op calls / wire bytes / wall and achieved GB/s from the
  ``comm.*`` spans (runtime/hostring.py), predicted-vs-achieved
  latency when a calibrated ``costmodel.json`` sits in the run dir,
  and per-rank straggler skew when the trace is a
  ``scripts/trace_merge.py`` merge of several ranks,
* stragglers: per-rank step-time skew when the trace is a
  ``scripts/trace_merge.py`` merge (its k-th-occurrence alignment
  puts every rank's k-th step on one clock), the ``train.rank_skew``
  gauge the elastic balancer emits at each rebalance boundary, and
  the rebalance audit trail (``split="elastic"`` records: per-rank
  shard counts, measured skew, whether ownership moved),
* checkpoint: the ``split="ckpt"`` audit trail — every save's
  format/tag/world/replication and per-rank vs total bytes, every
  restore's adopted tag with its peer-fetch / walk-back / stranded-
  write counts — plus per-rank ``elastic.checkpoint`` save walls from
  a merged trace (sharded saves should be balanced; the full format
  concentrates the write on rank 0),
* plan: the auto-parallel planner's ranked candidate table when a
  ``plan.json`` (``--strategy auto`` / autoplan/planner.py) sits in
  the run dir — the audit trail for why this run's strategy was
  chosen,
* hang autopsy: when the run dir holds ``flight-rank*.json`` dumps
  (what every surviving rank's always-on flight recorder writes on a
  collective deadline or transport poison, runtime/flightrec.py), the
  merged verdict — missing_rank / mismatch / straggler — with the
  per-rank evidence rows at the deciding occurrence,
* serving: TTFT percentiles plus the paged-KV saturation picture from
  ``split="serve"`` snapshots — peak pages in use, prefix-cache hit
  rate, and speculative accepted-tokens-per-verify when the engine ran
  with ``SpecConfig``.

Usage::

    python scripts/obs_report.py RUN_DIR [--top 10]
    python scripts/obs_report.py --trace trace.json --metrics m.jsonl

Works with either input alone: a chaos-drill dir usually has only the
JSONL (rollups + goodput), a bench dir maybe only the trace. In a run
dir with no ``trace.json``, a ``merged_trace.json`` (trace_merge
output) is picked up instead.
"""

import argparse
import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from pytorch_distributed_tpu.runtime.tracing import summarize_goodput  # noqa: E402
from pytorch_distributed_tpu.train.metrics import read_metrics  # noqa: E402
from pytorch_distributed_tpu.utils.timing import percentile  # noqa: E402


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument("run_dir", nargs="?", default=None,
                   help="directory holding trace.json and/or *.jsonl")
    p.add_argument("--trace", default=None, help="explicit trace.json path")
    p.add_argument("--metrics", action="append", default=None,
                   help="explicit metrics JSONL path (repeatable)")
    p.add_argument("--top", type=int, default=10,
                   help="how many widest spans to list")
    p.add_argument("--costmodel", default=None,
                   help="calibrated costmodel.json for the "
                   "achieved-vs-predicted comms comparison (default: "
                   "<run_dir>/costmodel.json when present)")
    p.add_argument("--plan", default=None,
                   help="auto-parallel plan.json to render (default: "
                   "<run_dir>/plan.json when present)")
    return p.parse_args(argv)


def _discover(args):
    trace_path, metric_paths = args.trace, list(args.metrics or [])
    costmodel_path, plan_path = args.costmodel, args.plan
    flight_dir = None
    if args.run_dir:
        if glob.glob(os.path.join(args.run_dir, "flight-rank*.json")):
            flight_dir = args.run_dir
        if trace_path is None:
            for name in ("trace.json", "merged_trace.json"):
                cand = os.path.join(args.run_dir, name)
                if os.path.isfile(cand):
                    trace_path = cand
                    break
        if not metric_paths:
            metric_paths = sorted(
                glob.glob(os.path.join(args.run_dir, "*.jsonl"))
            )
        if costmodel_path is None:
            cand = os.path.join(args.run_dir, "costmodel.json")
            costmodel_path = cand if os.path.isfile(cand) else None
        if plan_path is None:
            cand = os.path.join(args.run_dir, "plan.json")
            plan_path = cand if os.path.isfile(cand) else None
    return trace_path, metric_paths, costmodel_path, plan_path, flight_dir


def plan_section(plan_path, out):
    """Render the auto-parallel planner's ranked candidate table."""
    if not plan_path:
        return None
    from pytorch_distributed_tpu.autoplan.planner import format_plan

    try:
        with open(plan_path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        print(f"\n== Plan ==\n  (plan {plan_path} unreadable: {e})",
              file=out)
        return None
    print("\n== Plan ==", file=out)
    print(f"  source: {plan_path}", file=out)
    try:
        lines = format_plan(doc)
    except (KeyError, TypeError, AttributeError) as e:
        # a truncated/hand-edited/future-format plan must degrade to a
        # note, not abort the report's remaining sections (same
        # convention as an unreadable costmodel.json above)
        print(f"  (plan {plan_path} does not match the expected "
              f"schema: {type(e).__name__}: {e})", file=out)
        return None
    for line in lines:
        print("  " + line, file=out)
    return doc


def hang_section(flight_dir, out):
    """Render the flight-recorder hang autopsy when a run dir holds
    ``flight-rank*.json`` dumps — what every surviving rank writes on a
    collective deadline, a transport poison, or an elastic view-commit
    timeout (runtime/flightrec.py)."""
    if not flight_dir:
        return None
    from pytorch_distributed_tpu.runtime import flightrec

    try:
        dumps = flightrec.load_dumps(flight_dir)
    except ValueError as e:
        print(f"\n== Hang autopsy ==\n  (flight dumps unusable: {e})",
              file=out)
        return None
    if not dumps:
        return None
    verdict = flightrec.autopsy(dumps)
    print("\n== Hang autopsy ==", file=out)
    print(f"  source: {len(dumps)} flight dump(s) under {flight_dir} "
          f"(ranks {sorted(dumps)})", file=out)
    print(f"  verdict: {verdict['verdict']}", file=out)
    if verdict["victim_rank"] is not None:
        print(f"  victim:  rank {verdict['victim_rank']} at seq "
              f"{verdict['seq']} ({verdict['op']}, group "
              f"{verdict['group']})", file=out)
    print(f"  detail:  {verdict['detail']}", file=out)
    for r in verdict["evidence"]:
        state = r["state"]
        desc = ("left no dump" if state == "absent" else
                f"seq={r['seq']} {r['kind']}/{r['op']} "
                f"count={r['count']} [{state}]")
        print(f"    rank {r['rank']}: {desc}", file=out)
    print("  (full per-rank report: python scripts/hang_autopsy.py "
          f"{flight_dir})", file=out)
    return verdict


def load_trace(path):
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, list):  # bare-array trace_event form
        return {"traceEvents": doc, "otherData": {}}
    return doc


def span_stats_from_events(events):
    """Aggregate ``X`` events by name -> duration lists (seconds)."""
    durs = {}
    for ev in events:
        if ev.get("ph") == "X":
            durs.setdefault(ev["name"], []).append(
                float(ev.get("dur", 0.0)) / 1e6
            )
    return durs


def span_stats_from_rollups(records):
    """Rebuild the breakdown rows from ``split="trace"`` rollup records
    (the no-trace.json fallback); values are already aggregated."""
    rows = {}
    for r in records:
        if r.get("split") == "trace" and r.get("event") == "span_rollup":
            rows[r["span"]] = {
                k: r[k] for k in (
                    "count", "total_ms", "mean_ms", "p50_ms", "p95_ms",
                    "p99_ms", "max_ms", "bytes_total", "gb_per_s",
                ) if k in r
            }
    return rows


def comm_stats_from_events(events):
    """Per ``comm.*`` span name: calls / wall / exact wire bytes (from
    the span args) plus the mean payload and world size the cost model
    needs to predict against."""
    out = {}
    for ev in events:
        if ev.get("ph") != "X" or not str(ev.get("name", "")).startswith(
            "comm."
        ):
            continue
        a = ev.get("args") or {}
        st = out.setdefault(ev["name"], {
            "count": 0, "total_ms": 0.0, "bytes_total": 0,
            "payload_total": 0, "world": a.get("world", 0),
        })
        st["count"] += 1
        st["total_ms"] += float(ev.get("dur", 0.0)) / 1e3
        st["bytes_total"] += int(a.get("wire_bytes", 0))
        st["payload_total"] += int(a.get("payload_bytes", 0))
    for st in out.values():
        st["mean_ms"] = st["total_ms"] / st["count"]
        st["payload_mean"] = st["payload_total"] // max(st["count"], 1)
        if st["total_ms"] > 0:
            st["gb_per_s"] = st["bytes_total"] / (
                st["total_ms"] / 1e3
            ) / 1e9
    return out


def comms_section(events, rows, other, costmodel_path, out):
    """Render the per-op comms table (+ model comparison + rank skew)."""
    stats = comm_stats_from_events(events)
    if not stats:  # JSONL-rollup fallback: bytes but no payload/world
        stats = {
            n: dict(r) for n, r in rows.items()
            if n.startswith("comm.") and r.get("bytes_total")
        }
    skew = (other or {}).get("comm_skew") or {}
    if not stats and not skew:
        return
    print("\n== Comms ==", file=out)
    model = None
    if costmodel_path:
        from pytorch_distributed_tpu.runtime import costmodel as cm

        # every comm span since r16 records which transport carried it;
        # refuse to compare measurements against a model fit on a
        # DIFFERENT transport (a tcp β is ~an order of magnitude off an
        # shm one — the meas/pred column would be confidently wrong).
        # Pre-r16 traces carry no transport arg: no check possible.
        kinds = sorted({
            str((ev.get("args") or {}).get("transport"))
            for ev in events
            if ev.get("ph") == "X"
            and str(ev.get("name", "")).startswith("comm.")
            and (ev.get("args") or {}).get("transport")
        })
        try:
            model = cm.CostModel.load(costmodel_path)
        except (OSError, ValueError, KeyError, TypeError) as e:
            # missing/unreadable stays graceful (reports render without
            # the pred column) ...
            print(f"  (costmodel {costmodel_path} unreadable: {e})",
                  file=out)
        # "hostring" (the facade-sweep label for the native shm ring)
        # and "shm" (the ring's own span kind) are the same physical
        # transport — normalize before comparing
        alias = {"hostring": "shm"}
        kinds = sorted({alias.get(k, k) for k in kinds})
        mkind = (alias.get(model.transport, model.transport)
                 if model is not None else None)
        if model is not None and kinds and mkind not in kinds:
            # ... but a transport MISMATCH raises: silence here is a
            # wrong number in the report
            raise cm.CostModelUnavailable(
                f"cost model {costmodel_path!r} was calibrated on "
                f"transport {model.transport!r} but this trace's comm "
                f"spans ran on {kinds} — refit per transport "
                f"(`collective_bench.py --transport ...`) or point "
                f"--costmodel at the matching fit"
            )
        if model is not None:
            print(f"  cost model: {costmodel_path} "
                  f"(transport={model.transport})", file=out)
    if stats:
        header = ("op", "calls", "total_ms", "mean_ms", "moved_MB",
                  "GB/s", "pred_ms", "meas/pred")
        widths = [max(24, *(len(n) for n in stats))] + [9] * 7
        print("  " + _fmt_row(header, widths), file=out)
        for name in sorted(
            stats, key=lambda n: -stats[n].get("total_ms", 0.0)
        ):
            st = stats[name]
            pred_ms = ratio = "-"
            if (model is not None and st.get("payload_mean")
                    and st.get("world")):
                try:
                    p = model.predict(
                        name[len("comm."):], st["payload_mean"],
                        int(st["world"]),
                    )
                    pred_ms = f"{p.seconds * 1e3:.3f}" + (
                        "*" if p.extrapolated else ""
                    )
                    if p.seconds > 0:
                        ratio = f"{st['mean_ms'] / 1e3 / p.seconds:.2f}"
                except KeyError:
                    pass
            print("  " + _fmt_row(
                (name, int(st.get("count", 0)),
                 f"{st.get('total_ms', 0.0):.1f}",
                 f"{st.get('mean_ms', 0.0):.3f}",
                 f"{st.get('bytes_total', 0) / 1e6:.1f}",
                 f"{st.get('gb_per_s', 0.0):.2f}",
                 pred_ms, ratio),
                widths,
            ), file=out)
        if model is not None:
            print("  (pred_ms from the α–β fit at each op's mean "
                  "payload; * = outside the calibrated range)", file=out)
    # per-transport wire accounting (r16): every armed comm span also
    # bumps a cumulative ``comm.bytes.<transport>`` counter per process.
    # Counters are per-GROUP-life cumulative and restart at 0 on a fresh
    # ring (elastic re-mesh), so sum per-(pid, counter) increments like
    # the comm.sync counters below.
    tbytes: dict = {}
    tprev: dict = {}
    for ev in events:
        if ev.get("ph") == "C" and str(ev.get("name", "")).startswith(
            "comm.bytes."
        ):
            name = ev["name"]
            v = float((ev.get("args") or {}).get("value", 0.0))
            k = (ev.get("pid"), name)
            p = tprev.get(k, 0.0)
            tbytes[name] = tbytes.get(name, 0.0) + (
                v - p if v >= p else v
            )
            tprev[k] = v
    if tbytes:
        cross = tbytes.get("comm.bytes.tcp", 0.0)
        parts = ", ".join(
            f"{n[len('comm.bytes.'):]} {v / 1e6:.2f} MB"
            for n, v in sorted(tbytes.items())
        )
        print(
            f"  Cross-host bytes: {cross / 1e6:.2f} MB over tcp "
            f"(per transport: {parts})", file=out,
        )
        stats["comm.bytes"] = {
            n[len("comm.bytes."):]: int(v) for n, v in tbytes.items()
        }
    # overlapped grad sync (r14): the engine's cumulative exposed/hidden
    # counters — how much of the comm wall the main thread actually
    # blocked on vs how much ran under concurrent work. Counters are
    # cumulative PER ENGINE LIFE and restart at 0 when the engine is
    # rebuilt (elastic re-mesh, reset_engine), so sum the per-(rank,
    # counter) increments: a drop below the previous value marks a
    # fresh engine whose reading counts in full.
    expose: dict = {}
    prev: dict = {}
    for ev in events:
        if ev.get("ph") == "C" and str(ev.get("name", "")).startswith(
            "comm.sync."
        ):
            name = ev["name"]
            v = float((ev.get("args") or {}).get("value", 0.0))
            k = (ev.get("pid"), name)
            p = prev.get(k, 0.0)
            expose[name] = expose.get(name, 0.0) + (
                v - p if v >= p else v
            )
            prev[k] = v
    if expose:
        exp = expose.get("comm.sync.exposed_s", 0.0)
        hid = expose.get("comm.sync.hidden_s", 0.0)
        total = exp + hid
        stats["comm.sync.overlap"] = {
            "exposed_s": exp, "hidden_s": hid,
            **({"exposed_ratio": exp / total} if total > 0 else {}),
        }
        print(
            f"  grad-sync overlap: comm exposed {exp:.3f}s / hidden "
            f"{hid:.3f}s"
            + (f" (exposed ratio {exp / total:.2f})" if total > 0
               else ""),
            file=out,
        )
    if skew:
        print("  per-rank straggler skew (merged trace):", file=out)
        for name, s in sorted(skew.items()):
            print(
                f"    {name:<24} x{s['occurrences']:<5} "
                f"mean={s['skew_ms_mean']:.3f}ms "
                f"p95={s['skew_ms_p95']:.3f}ms "
                f"max={s['skew_ms_max']:.3f}ms "
                f"({s['ranks']} ranks)", file=out,
            )
    return stats


#: spans that mean "one training step" — the unit the per-rank
#: straggler comparison is over (the trainer's and the elastic
#: engine's step sections respectively)
STEP_SPANS = ("train.step", "elastic.step")


def stragglers_section(events, records, out):
    """Per-rank step-time skew + the heterogeneity balancer's audit.

    Three inputs, each optional: merged-trace step spans (pid = rank
    after trace_merge, so per-rank step walls line up on one clock),
    the ``train.rank_skew`` counter the rebalancer emits (max/min
    per-microshard seconds across ranks as allgathered — the quantity
    assignments are derived from), and ``split="elastic"`` rebalance
    records (what the balancer actually did about it)."""
    per_rank = {}
    for ev in events:
        if ev.get("ph") == "X" and ev.get("name") in STEP_SPANS:
            per_rank.setdefault(ev.get("pid"), []).append(
                float(ev.get("dur", 0.0)) / 1e3
            )
    gauge = [
        float((ev.get("args") or {}).get("value", 0.0))
        for ev in events
        if ev.get("ph") == "C" and ev.get("name") == "train.rank_skew"
    ]
    rebalances = [
        r for r in records
        if r.get("split") == "elastic" and r.get("event") == "rebalance"
    ]
    if (len(per_rank) < 2) and not gauge and not rebalances:
        return None
    print("\n== Stragglers ==", file=out)
    summary = {}
    if len(per_rank) >= 2:  # skew needs a merged multi-rank trace
        means = {
            r: sum(d) / len(d) for r, d in per_rank.items() if d
        }
        skew = max(means.values()) / min(means.values())
        summary["step_skew"] = round(skew, 4)
        summary["ranks"] = len(means)
        print(
            f"  per-rank step time (merged trace, "
            f"{min(len(d) for d in per_rank.values())} steps/rank):",
            file=out,
        )
        for r in sorted(means):
            d = per_rank[r]
            print(
                f"    rank{r}: mean={means[r]:.2f}ms "
                f"p95={percentile(d, 95):.2f}ms max={max(d):.2f}ms",
                file=out,
            )
        print(
            f"  step-time skew (slowest/fastest rank): {skew:.2f}x",
            file=out,
        )
    if gauge:
        summary["rank_skew_gauge"] = gauge[-1]
        print(
            f"  train.rank_skew gauge: last {gauge[-1]:.2f}x, max "
            f"{max(gauge):.2f}x over {len(gauge)} rebalance "
            f"boundar{'y' if len(gauge) == 1 else 'ies'} (measured "
            f"per-microshard seconds, max/min across ranks)", file=out,
        )
    if rebalances:
        moved = sum(1 for r in rebalances if r.get("changed"))
        summary["rebalances"] = len(rebalances)
        summary["rebalances_changed"] = moved
        print(
            f"  rebalances: {len(rebalances)} boundar"
            f"{'y' if len(rebalances) == 1 else 'ies'}, ownership moved "
            f"at {moved}", file=out,
        )
        for r in rebalances:
            print(
                f"    step {r.get('step', '?'):>6}  "
                f"counts={r.get('counts')}  "
                f"skew={r.get('skew', 0.0):.2f}x  "
                f"({r.get('reason', '?')}"
                f"{', moved' if r.get('changed') else ', unchanged'})",
                file=out,
            )
    return summary


def pipeline_section(events, out):
    """Per-stage pipeline accounting (r20) from merged-trace
    ``pipeline.fwd``/``pipeline.bwd`` spans: busy vs window time, the
    idle (bubble) fraction, and the exposed-link share per stage.

    Whole-run numbers: step 0's compiles and the inter-step optimizer
    boundaries count as idle here, so these fractions read HIGH
    relative to the analytic ``(S-1)/(V*M+S-1)`` — the bench's
    steady-state-windowed measurement is the number the planner's
    pricing is checked against; this section is the triage view."""
    from pytorch_distributed_tpu.parallel.pipeline_schedule import (
        pipeline_trace_stats,
    )

    stats = pipeline_trace_stats(events)
    if not stats:
        return None
    print("\n== Pipeline ==", file=out)
    print(
        f"  {len(stats)} stage(s) with schedule spans (whole-run "
        f"window: compiles + step boundaries count as idle):", file=out,
    )
    for rank, s in stats.items():
        print(
            f"    stage{rank}: busy={s['busy_s']:.2f}s "
            f"window={s['window_s']:.2f}s bubble={s['bubble']:.3f} "
            f"link={s['link_s']:.2f}s "
            f"({s['link_s'] / s['window_s']:.3f} of window)", file=out,
        )
    worst = max(stats.values(), key=lambda s: s["bubble"])
    return {
        "stages": len(stats),
        "max_bubble": round(worst["bubble"], 4),
        "max_link_ratio": round(
            max(s["link_s"] / s["window_s"] for s in stats.values()), 4
        ),
    }


def fleet_section(records, out):
    """The serving-fleet picture (r18): per-engine telemetry + the
    router's migration/replay audit.

    Fires only on fleet-shaped runs — ``split="serve"`` records that
    carry the ``engine_id`` label (a lone engine omits it and keeps the
    single-engine Serving section below), or router ``migrate``/
    ``replay`` records. Per engine: request counts and TTFT
    percentiles from ``event="request"``, last slot occupancy from
    ``event="snapshot"``. Fleet-wide: KV migration totals (frames,
    wire bytes, payload bytes, pages) and evict-and-replay counts —
    the at-least-once cost of surviving an engine loss."""
    serve = [r for r in records if r.get("split") == "serve"]
    # migrate/replay also carry engine_id (the source/lost engine) —
    # only request/snapshot records describe an engine's own traffic
    labeled = [
        r for r in serve
        if r.get("engine_id")
        and r.get("event") in ("request", "snapshot")
    ]
    migrates = [r for r in serve if r.get("event") == "migrate"]
    replays = [r for r in serve if r.get("event") == "replay"]
    if not labeled and not migrates and not replays:
        return None
    print("\n== Fleet ==", file=out)
    summary = {}
    per_engine = {}
    for r in labeled:
        per_engine.setdefault(r["engine_id"], []).append(r)
    if per_engine:
        summary["engines"] = len(per_engine)
        print(f"  {len(per_engine)} engine(s) in the merged stream:",
              file=out)
        for eid in sorted(per_engine):
            recs = per_engine[eid]
            done = [
                r for r in recs
                if r.get("event") == "request"
                and r.get("status") == "completed"
            ]
            ttfts = [r["ttft_ms"] for r in done if "ttft_ms" in r]
            snaps = [r for r in recs if r.get("event") == "snapshot"]
            bits = [f"{len(done)} completed"]
            if ttfts:
                bits.append(
                    f"ttft p50={percentile(ttfts, 50):.1f}ms "
                    f"p99={percentile(ttfts, 99):.1f}ms"
                )
            if snaps:
                bits.append(
                    f"occupancy last "
                    f"{snaps[-1].get('slot_occupancy', 0.0):.2f}"
                )
            print(f"    {eid:<8} " + "  ".join(bits), file=out)
    if migrates:
        nbytes = sum(int(r.get("nbytes", 0)) for r in migrates)
        payload = sum(int(r.get("payload_nbytes", 0)) for r in migrates)
        pages = sum(int(r.get("n_pages", 0)) for r in migrates)
        summary["migrated_frames"] = len(migrates)
        summary["migrated_nbytes"] = nbytes
        summary["migrated_pages"] = pages
        print(
            f"  kv migration: {len(migrates)} frame(s), {pages} "
            f"page(s), {nbytes / 1e6:.2f}MB wire "
            f"({payload / 1e6:.2f}MB KV payload)", file=out,
        )
    if replays:
        lost = sorted({r.get("engine_id", "?") for r in replays})
        summary["replays"] = len(replays)
        summary["engines_lost"] = lost
        print(
            f"  replays: {len(replays)} request(s) re-admitted after "
            f"losing {', '.join(lost)} <-- at-least-once: lost decode "
            f"work is re-run, outputs stay deterministic", file=out,
        )
    return summary


def checkpoint_section(events, records, out):
    """The checkpoint audit trail + per-rank save cost (r17).

    Two inputs, each optional: ``split="ckpt"`` records the elastic
    engine writes (every save names its format/tag/world/replication
    and — sharded — this rank's bytes vs the world total; every restore
    names the tag it adopted, the world that WROTE it, and how hard the
    loader had to work: peer fetches, epochs walked back, stranded
    writes mopped up), and merged-trace ``elastic.checkpoint`` spans
    (pid = rank after trace_merge), which show whether save cost is
    balanced across ranks — the point of sharding it."""
    recs = [r for r in records if r.get("split") == "ckpt"]
    saves = [r for r in recs if r.get("event") == "save"]
    restores = [r for r in recs if r.get("event") == "restore"]
    per_rank = {}
    for ev in events:
        if ev.get("ph") == "X" and ev.get("name") == "elastic.checkpoint":
            per_rank.setdefault(ev.get("pid"), []).append(
                float(ev.get("dur", 0.0)) / 1e3
            )
    if not recs and len(per_rank) < 2:
        return None
    print("\n== Checkpoint ==", file=out)
    summary = {
        "saves": len(saves),
        "restores": len(restores),
        "peer_fetches": sum(
            int(r.get("peer_fetches", 0)) for r in restores
        ),
        "walked_back": sum(
            int(r.get("walked_back", 0)) for r in restores
        ),
    }
    if recs:
        sharded = sum(1 for r in saves if r.get("format") == "sharded")
        print(
            f"  saves: {len(saves)} ({sharded} sharded, "
            f"{len(saves) - sharded} full); restores: {len(restores)}",
            file=out,
        )
        for r in saves:
            if r.get("format") == "sharded":
                detail = (
                    f"world {r.get('world', '?')} repl "
                    f"{r.get('replication', '?')}  rank "
                    f"{r.get('rank_bytes', 0) / 1e6:.2f}MB / total "
                    f"{r.get('total_bytes', 0) / 1e6:.2f}MB"
                )
            else:
                detail = f"world {r.get('world', '?')} (gather to rank 0)"
            print(
                f"    step {r.get('step', '?'):>6}  save     "
                f"{r.get('format', '?'):<8} tag {r.get('tag', '?'):<12} "
                f"{detail}", file=out,
            )
        for r in restores:
            extras = []
            if r.get("peer_fetches"):
                extras.append(
                    f"peer_fetches {r['peer_fetches']} <-- sole-copy "
                    f"loss repaired from the replication peer"
                )
            if r.get("walked_back"):
                extras.append(
                    f"walked back {r['walked_back']} epoch(s) <-- "
                    f"INVESTIGATE (a whole checkpoint was unrestorable)"
                )
            if r.get("recovered"):
                extras.append(f"recovered {r['recovered']}")
            print(
                f"    step {r.get('step', '?'):>6}  restore  "
                f"tag {r.get('tag', '?'):<12} wrote by world "
                f"{r.get('ckpt_world', '?')} -> step "
                f"{r.get('restored_step', '?')}"
                + ("  " + "; ".join(extras) if extras else ""),
                file=out,
            )
    if len(per_rank) >= 2:
        totals = {r: sum(d) for r, d in per_rank.items()}
        balance = max(totals.values()) / max(min(totals.values()), 1e-9)
        summary["save_wall_skew"] = round(balance, 4)
        print(
            f"  per-rank save wall (merged trace, elastic.checkpoint):",
            file=out,
        )
        for r in sorted(per_rank):
            d = per_rank[r]
            print(
                f"    rank{r}: {len(d)} save(s), total "
                f"{totals[r]:.2f}ms, max {max(d):.2f}ms", file=out,
            )
        print(
            f"  save-wall skew (slowest/fastest rank): {balance:.2f}x "
            f"(sharded saves should be balanced; the full format "
            f"concentrates the write on rank 0)", file=out,
        )
    return summary


def _fmt_row(cols, widths):
    return "  ".join(str(c).rjust(w) for c, w in zip(cols, widths))


def phase_table(rows, wall_ms):
    header = ("span", "count", "total_ms", "mean_ms", "p50_ms",
              "p95_ms", "p99_ms", "max_ms", "%wall")
    widths = [max(28, *(len(n) for n in rows))] + [8] * 8 if rows else []
    if not rows:
        return ["  (no spans)"]
    out = [_fmt_row(header, widths)]
    for name in sorted(rows, key=lambda n: -rows[n].get("total_ms", 0.0)):
        r = rows[name]
        pct = (
            100.0 * r.get("total_ms", 0.0) / wall_ms if wall_ms else 0.0
        )
        out.append(_fmt_row(
            (name, int(r.get("count", 0)),
             f"{r.get('total_ms', 0.0):.1f}",
             f"{r.get('mean_ms', 0.0):.2f}",
             f"{r.get('p50_ms', 0.0):.2f}",
             f"{r.get('p95_ms', 0.0):.2f}",
             f"{r.get('p99_ms', 0.0):.2f}",
             f"{r.get('max_ms', 0.0):.2f}",
             f"{pct:.1f}"),
            widths,
        ))
    return out


def report(trace_path, metric_paths, top_n=10, out=None,
           costmodel_path=None, plan_path=None, flight_dir=None):
    # resolve the CURRENT sys.stdout, not import-time's: under pytest
    # capture an import-time default would pin the first importing
    # test's capture stream and every later caller would print into it
    out = out if out is not None else sys.stdout
    records = []
    for mp in metric_paths:
        try:
            records.extend(read_metrics(mp))
        except OSError as e:
            print(f"(metrics {mp} unreadable: {e})", file=out)

    events, other = [], {}
    if trace_path:
        try:
            doc = load_trace(trace_path)
            events = doc.get("traceEvents", [])
            other = doc.get("otherData", {}) or {}
        except (OSError, ValueError) as e:
            print(f"(trace {trace_path} unreadable: {e})", file=out)

    # -- step-phase breakdown ---------------------------------------------
    print("== Step-phase breakdown ==", file=out)
    if events:
        durs = span_stats_from_events(events)
        xs = [e for e in events if e.get("ph") == "X"]
        wall_ms = (
            (max(e["ts"] + e.get("dur", 0.0) for e in xs)
             - min(e["ts"] for e in xs)) / 1e3 if xs else 0.0
        )
        rows = {
            name: {
                "count": len(d),
                "total_ms": sum(d) * 1e3,
                "mean_ms": sum(d) / len(d) * 1e3,
                "p50_ms": percentile(d, 50) * 1e3,
                "p95_ms": percentile(d, 95) * 1e3,
                "p99_ms": percentile(d, 99) * 1e3,
                "max_ms": max(d) * 1e3,
            }
            for name, d in durs.items()
        }
        src = f"trace: {trace_path}, wall {wall_ms / 1e3:.2f}s"
    else:
        rows = span_stats_from_rollups(records)
        wall_ms = sum(r.get("total_ms", 0.0) for r in rows.values())
        src = "JSONL span rollups (no trace.json; %wall = share of traced time)"
    print(f"  source: {src}", file=out)
    for line in phase_table(rows, wall_ms):
        print("  " + line, file=out)

    # -- widest spans ------------------------------------------------------
    if events:
        print(f"\n== Top {top_n} widest spans ==", file=out)
        widest = sorted(
            (e for e in events if e.get("ph") == "X"),
            key=lambda e: -e.get("dur", 0.0),
        )[:top_n]
        for e in widest:
            args_note = f"  args={e['args']}" if e.get("args") else ""
            print(
                f"  {e.get('dur', 0.0) / 1e3:10.2f} ms  {e['name']:<28}"
                f" @ t={e['ts'] / 1e6:.3f}s tid={e.get('tid')}{args_note}",
                file=out,
            )

    # -- recompile sentinel ------------------------------------------------
    print("\n== Recompiles (after warm-up) ==", file=out)
    # one event="recompiles" record per attempt (each fit() has a fresh
    # tracer), so SUM across records; the trace.json duplicates the last
    # surviving attempt's counts, so merge it by max, not by adding
    jsonl_rec = {}
    for r in records:
        if r.get("split") == "trace" and r.get("event") == "recompiles":
            for k, v in r.items():
                if k.startswith("recompiles."):
                    name = k[len("recompiles."):]
                    jsonl_rec[name] = jsonl_rec.get(name, 0) + int(v)
    recompiles = dict(other.get("recompiles") or {})
    for name, n in jsonl_rec.items():
        recompiles[name] = max(recompiles.get(name, 0), n)
    if recompiles:
        for name, n in sorted(recompiles.items()):
            print(f"  {name}: {n} steady-state recompile(s)  <-- "
                  f"INVESTIGATE (silent 100x regression shape)", file=out)
    else:
        print("  none — every jitted callable compiled once", file=out)

    # -- comms -------------------------------------------------------------
    comms = comms_section(events, rows, other, costmodel_path, out)

    # -- stragglers (r15: heterogeneity picture) ---------------------------
    stragglers = stragglers_section(events, records, out)

    # -- pipeline stages (r20: per-stage busy/bubble/link picture) ---------
    pipe = pipeline_section(events, out)

    # -- checkpoint audit (r17: sharded save/restore trail) ----------------
    ckpt = checkpoint_section(events, records, out)

    # -- serving fleet (r18: per-engine telemetry + migration audit) -------
    fleet = fleet_section(records, out)

    # -- auto-parallel plan ------------------------------------------------
    plan_doc = plan_section(plan_path, out)

    # -- goodput -----------------------------------------------------------
    print("\n== Goodput ==", file=out)
    g = summarize_goodput(records)
    if g["attempts_recorded"]:
        print(
            f"  goodput {g['goodput_pct']:.1f}% over "
            f"{g['wall_s']:.1f}s wall ({g['attempts_recorded']} "
            f"attempt(s) recorded)", file=out,
        )
        for k in sorted(k for k in g if k.endswith("_s") and k != "wall_s"):
            print(f"    {k:<16} {g[k]:10.2f}", file=out)
    else:
        print("  no goodput records in the metrics stream", file=out)
    # elastic-world membership transitions ride the same stream
    # (train/elastic_world.py, split="elastic"): each in-process resize
    # names its epochs, the surviving world size, and what it cost —
    # the goodput 'resize' bucket, itemized
    views = [
        r for r in records
        if r.get("split") == "elastic" and r.get("event") == "view_change"
    ]
    if views:
        total_resize = sum(float(r.get("resize_s", 0.0)) for r in views)
        print(
            f"  membership: {len(views)} view change(s), "
            f"{total_resize:.2f}s total resize cost", file=out,
        )
        for r in views:
            print(
                f"    step {r.get('step', '?'):>6}  epoch "
                f"{r.get('from_epoch', '?')} -> {r.get('epoch', '?')}  "
                f"world {r.get('world_size', '?')}  "
                f"({r.get('reason', '?')}, {r.get('resize_s', 0.0):.2f}s)",
                file=out,
            )
        g["view_changes"] = len(views)
        g["resize_total_s"] = round(total_resize, 4)

    # -- serve telemetry, if present --------------------------------------
    serve_recs = [r for r in records if r.get("split") == "serve"]
    ttfts = [r["ttft_ms"] for r in serve_recs if "ttft_ms" in r]
    snaps = [r for r in serve_recs if r.get("event") == "snapshot"]
    serve = {}
    if ttfts or snaps:
        print("\n== Serving ==", file=out)
    if ttfts:
        serve["ttft_n"] = len(ttfts)
        print(
            f"  TTFT n={len(ttfts)} p50={percentile(ttfts, 50):.1f}ms "
            f"p95={percentile(ttfts, 95):.1f}ms "
            f"p99={percentile(ttfts, 99):.1f}ms", file=out,
        )
    if snaps:
        # the paged-pool / speculation gauges ride the same snapshot
        # records (serve/telemetry.py): report the saturation picture —
        # peak across snapshots for occupancy, latest for cumulative
        # counters
        last = snaps[-1]
        peak_slots = max(s.get("slots_occupied", 0) for s in snaps)
        serve["snapshots"] = len(snaps)
        print(
            f"  slots: peak {peak_slots}/{last.get('slots_total', '?')} "
            f"occupied over {len(snaps)} snapshots, "
            f"{last.get('decode_ticks', 0)} decode ticks", file=out,
        )
        if "pages_in_use" in last:
            peak_pages = max(s.get("pages_in_use", 0) for s in snaps)
            serve["peak_pages"] = peak_pages
            print(
                f"  kv pool: peak {peak_pages}/"
                f"{last.get('pages_total', '?')} pages in use "
                f"({100.0 * peak_pages / max(last.get('pages_total', 1), 1):.0f}"
                f"% of pool), prefix hit rate "
                f"{last.get('prefix_hit_rate', 0.0):.3f} "
                f"(fraction of prompt tokens served from shared pages)",
                file=out,
            )
        if last.get("spec_verifies"):
            apv = last.get("spec_accepted", 0) / last["spec_verifies"]
            serve["spec_accepted_per_verify"] = apv
            print(
                f"  speculation: {last['spec_verifies']} verifies, "
                f"{last.get('spec_accepted', 0)}/"
                f"{last.get('spec_drafted', 0)} drafts accepted "
                f"({apv:.2f} accepted tokens/verify; each verify also "
                f"emits its correction token)", file=out,
            )
    # -- hang autopsy, if the run left flight dumps -----------------------
    hang = hang_section(flight_dir, out)

    return {"spans": rows, "recompiles": recompiles, "goodput": g,
            "comms": comms or {}, "stragglers": stragglers or {},
            "pipeline": pipe or {}, "checkpoint": ckpt or {},
            "fleet": fleet or {}, "plan": plan_doc, "serve": serve,
            "hang": hang}


def main(argv=None):
    args = parse_args(argv)
    if not args.run_dir and not args.trace and not args.metrics:
        print("nothing to report: pass RUN_DIR or --trace/--metrics",
              file=sys.stderr)
        return 2
    (trace_path, metric_paths, costmodel_path, plan_path,
     flight_dir) = _discover(args)
    if (not trace_path and not metric_paths and not plan_path
            and not flight_dir):
        print(
            f"no trace.json, *.jsonl, plan.json or flight-rank*.json "
            f"found under {args.run_dir!r}", file=sys.stderr,
        )
        return 2
    report(trace_path, metric_paths, top_n=args.top,
           costmodel_path=costmodel_path, plan_path=plan_path,
           flight_dir=flight_dir)
    return 0


if __name__ == "__main__":
    sys.exit(main())
