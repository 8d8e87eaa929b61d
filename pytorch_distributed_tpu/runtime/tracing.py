"""Unified runtime tracing + goodput accounting.

The repo could tell you *that* a step was slow, not *where* the time
went. This module is the measurement substrate: a process-wide span
recorder buffering Chrome ``trace_event`` records, three production
sentinels on the same stream, and the goodput account that turns a
chaos-drill's wall clock into productive-vs-recovery seconds.

* :func:`span` — ``with span("data.fetch"):`` around any host-side
  phase. Complete ("X") events carry microsecond ts/dur, pid/tid, so
  ``trace.json`` loads directly in Perfetto / chrome://tracing and
  spans from loader threads land on their own track. Every armed span
  also carries, in its ``args``, a ``span_id`` from a tracer-wide
  counter and the ``parent_id`` of the live span enclosing it on the
  same thread (``None`` at the top), so a reader rebuilds the tree of
  what caused what without comparing intervals; ``sp.set(k=v)`` on a
  live span adds args known only once its body ran.
* :func:`instant` / :func:`counter` — point events and gauges (e.g.
  ``device_bytes_in_use``) on the same timeline.
* :func:`note_compiles` — the recompile sentinel: instrumented code
  reports its jitted callable's compile count (serve's
  ``decode_compiles``/``prefill_compiles`` counters, the Trainer's
  ``jit_cache_size`` poll); the FIRST observation is the warm-up
  baseline, any later increase logs loudly — a steady-state loop that
  recompiles is the classic silent 100x regression.
* :class:`GoodputAccount` — classifies wall time into ``productive`` /
  ``stalled`` / ``recovering`` (+ ``checkpoint``) buckets; whatever is
  not attributed is ``other_s``, so the buckets always sum to wall.
* ``Tracer.write_rollups`` — per-span count/total/mean/p50/p95/p99
  through the existing MetricsWriter JSONL protocol
  (``split="trace"``), consumed by ``scripts/obs_report.py``. Spans
  whose args carry ``wire_bytes`` (the ``comm.*`` collective spans,
  runtime/hostring.py) additionally accumulate an exact byte total, so
  rollups report achieved GB/s per op.
* :func:`set_meta` — process-level trace metadata (rank, world size,
  measured clock offset). Lives at module scope, NOT on the tracer, so
  a group initialised before the tracer is armed still stamps the
  export; ``scripts/trace_merge.py`` aligns per-rank traces with it.

Overhead discipline (same as runtime/faults.py): unarmed — the
production default — every instrumentation site is a single
module-global ``is None`` test. A kwarg-free ``span()`` then returns
one shared no-op object: no allocation, no clock read. Sites that
attach args (``span("ingest.fetch", n=len(indices))``) additionally
pay Python's kwargs dict + argument evaluation before the is-None
test — keep hot-path sites kwarg-free or ~ms-grained. Pinned by
bench.py's ``observability`` phase: traced-vs-untraced < 2%.

Arming::

    tracer = tracing.configure("/tmp/run")     # or TrainerConfig.trace
    ...                                        # instrumented code runs
    tracer.export()                            # -> /tmp/run/trace.json
    tracer.write_rollups(metrics_writer)       # -> JSONL rollups
    tracing.clear()

or scoped (tests)::

    with tracing.enabled() as t:
        ...

This module deliberately imports no jax: it must stay importable (and
cheap) from the data-loader producer thread and from host-only tools.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from pytorch_distributed_tpu.utils.logging import get_logger
from pytorch_distributed_tpu.utils.timing import percentile

logger = get_logger(__name__)

#: goodput bucket names every summary reports (extra buckets are kept too).
#: ``resize`` is the in-process elastic window (train/elastic_world.py):
#: peer-loss detection -> membership re-rendezvous -> in-memory re-shard —
#: distinct from ``recovering`` (restore + replay), so the resize cost is
#: a priced fact the bench's ``elastic`` phase compares against restart.
#: ``rebalance`` (r15) is the heterogeneity balancer's own overhead — the
#: rate allgather + assignment derivation at each rebalance boundary
#: (train/balance.py) — priced separately so the balancing win the bench
#: ``hetero`` phase claims is net of what the balancer itself costs.
GOODPUT_BUCKETS = ("productive", "stalled", "recovering", "checkpoint",
                   "resize", "rebalance")


class _NullSpan:
    """The disabled path's shared no-op span: reentrant, allocation-free."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        """Late args of a live span; ignored while disarmed."""


_NULL_SPAN = _NullSpan()

_tracer: Optional["Tracer"] = None

# process-level trace metadata (rank / world_size / clock_offset_s, ...):
# survives configure()/clear() cycles and is snapshotted into every
# export's otherData, whichever side of the arming it was stamped on
_meta: Dict[str, Any] = {}


def set_meta(**kv) -> None:
    """Stamp process-level metadata into every later trace export."""
    _meta.update(kv)


def get_meta() -> Dict[str, Any]:
    return dict(_meta)


class _Span:
    """One live span: id, parent and clock read on enter, record
    appended on exit."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_stack")

    def __init__(self, tracer: "Tracer", name: str, args):
        self._tracer = tracer
        self._name = name
        # this span's own dict (ids and late args land in it): callers
        # hand in None, or one dict for every call of a site (ddp.py)
        self._args = dict(args) if args else {}

    def __enter__(self):
        t = self._tracer
        stack = self._stack = t._live_stack()
        span_id = next(t._ids)
        self._args["span_id"] = span_id
        self._args["parent_id"] = stack[-1] if stack else None
        stack.append(span_id)
        self._t0 = t._clock()
        return self

    def __exit__(self, *exc):
        t = self._tracer
        t1 = t._clock()
        self._stack.pop()
        t.complete(self._name, self._args, self._t0, t1)
        return False

    def set(self, **args) -> None:
        """Args known only after the body ran (what a step did)."""
        self._args.update(args)


class Tracer:
    """Buffers trace events + per-span rollups; thread-safe.

    ``trace_dir`` is where :meth:`export` writes ``trace.json`` (None =
    in-memory only, export takes an explicit path). Memory is bounded
    on BOTH sides of a run longer than the buffers: the event buffer is
    capped at ``max_events`` — beyond it events are DROPPED (loudly,
    once, with the drop count recorded in the export's ``otherData``)
    — while the rollup aggregates keep exact count/total/max forever
    (three scalars per span name) and bound the percentile sample at
    ``sample_cap`` recent durations per name, so a day-long traced
    serve run cannot grow host memory without limit.
    """

    def __init__(
        self,
        trace_dir: Optional[str] = None,
        *,
        max_events: int = 200_000,
        sample_cap: int = 8192,
        clock=time.perf_counter,
    ):
        self.trace_dir = trace_dir
        self.max_events = int(max_events)
        self.sample_cap = int(sample_cap)
        self._clock = clock
        self._t0 = clock()
        self._wall0 = time.time()
        self._pid = os.getpid()
        self._lock = threading.Lock()
        self._ids = itertools.count(1)  # span ids; next() is atomic
        self._local = threading.local()  # .stack: this thread's live ids
        self._events: List[Dict[str, Any]] = []
        self.dropped = 0
        self._stats: Dict[str, list] = {}  # name -> [count, total_s, max_s]
        self._samples: Dict[str, Any] = {}  # name -> bounded recent durations
        self._bytes: Dict[str, int] = {}  # name -> exact wire-byte total
        self._compiles: Dict[str, int] = {}  # last observed compile count
        self.recompiles: Dict[str, int] = {}  # compiles AFTER warm-up

    # -- recording ---------------------------------------------------------
    def span(self, name: str, args: Optional[dict] = None) -> _Span:
        return _Span(self, name, args)

    def _live_stack(self) -> List[int]:
        """Ids of the calling thread's live spans, outermost first."""
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    def _ts_us(self, t: float) -> float:
        return (t - self._t0) * 1e6

    def _append(self, ev: Dict[str, Any]) -> None:
        # caller holds self._lock
        if len(self._events) >= self.max_events:
            if self.dropped == 0:
                logger.warning(
                    "trace buffer full (%d events) — dropping further "
                    "events; rollup aggregates keep counting",
                    self.max_events,
                )
            self.dropped += 1
            return
        self._events.append(ev)

    def complete(self, name: str, args, t0: float, t1: float) -> None:
        """Record a finished span (also the hook tests feed directly)."""
        ev = {
            "name": name,
            "ph": "X",
            "ts": round(self._ts_us(t0), 3),
            "dur": round((t1 - t0) * 1e6, 3),
            "pid": self._pid,
            "tid": threading.get_ident(),
        }
        if args:
            ev["args"] = args
        dur = t1 - t0
        with self._lock:
            st = self._stats.get(name)
            if st is None:
                st = self._stats[name] = [0, 0.0, 0.0]
                self._samples[name] = collections.deque(
                    maxlen=self.sample_cap
                )
            st[0] += 1
            st[1] += dur
            if dur > st[2]:
                st[2] = dur
            self._samples[name].append(dur)
            if args:
                wb = args.get("wire_bytes")
                if wb:  # exact like count/total: scalars, never sampled
                    self._bytes[name] = self._bytes.get(name, 0) + int(wb)
            self._append(ev)

    def instant(self, name: str, args: Optional[dict] = None) -> None:
        ev = {
            "name": name,
            "ph": "i",
            "s": "p",  # process-scoped marker line
            "ts": round(self._ts_us(self._clock()), 3),
            "pid": self._pid,
            "tid": threading.get_ident(),
        }
        if args:
            ev["args"] = args
        with self._lock:
            self._append(ev)

    def name_thread(self, name: str) -> None:
        """Label the CALLING thread's track in the export (Chrome "M"
        thread_name metadata) — the grad-sync comm thread names its own
        lane so ``comm.*`` spans issued off the main thread read as
        "grad-sync-comm" in Perfetto, not a bare thread id."""
        ev = {
            "name": "thread_name",
            "ph": "M",
            "pid": self._pid,
            "tid": threading.get_ident(),
            "args": {"name": name},
        }
        with self._lock:
            self._append(ev)

    def counter(self, name: str, value: float) -> None:
        ev = {
            "name": name,
            "ph": "C",
            "ts": round(self._ts_us(self._clock()), 3),
            "pid": self._pid,
            "tid": threading.get_ident(),
            "args": {"value": value},
        }
        with self._lock:
            self._append(ev)

    # -- recompile sentinel ------------------------------------------------
    def note_compiles(self, name: str, n: int) -> None:
        """Report a callable's cumulative compile count.

        The first report is the warm-up baseline (compiling once is the
        contract, not a bug); every later increase is a steady-state
        recompile — counted, marked on the timeline, and logged loudly.
        """
        with self._lock:
            prev = self._compiles.get(name)
            self._compiles[name] = n
            if prev is None or n <= prev:
                return
            new = n - prev
            self.recompiles[name] = self.recompiles.get(name, 0) + new
        logger.warning(
            "RECOMPILE detected: %r compiled %d more time(s) after "
            "warm-up (now %d total) — a steady-state loop that "
            "recompiles is the classic silent 100x regression; look for "
            "changing shapes/dtypes/weak types/static args",
            name, new, n,
        )
        self.instant("recompile", {"callable": name, "total_compiles": n})

    # -- aggregates --------------------------------------------------------
    def rollups(self) -> Dict[str, Dict[str, float]]:
        """Per-span-name aggregate: count/total/mean/p50/p95/p99/max.

        count/total/mean/max are exact over the whole run; percentiles
        come from the ``sample_cap`` most recent durations per name.
        Spans that recorded ``wire_bytes`` args (the ``comm.*`` sites)
        also report the exact byte total and achieved GB/s.
        """
        with self._lock:
            items = {
                k: (list(st), list(self._samples[k]))
                for k, st in self._stats.items()
            }
            byte_totals = dict(self._bytes)
        out: Dict[str, Dict[str, float]] = {}
        for name in sorted(items):
            (count, total, mx), sample = items[name]
            out[name] = {
                "count": count,
                "total_ms": total * 1e3,
                "mean_ms": total / count * 1e3,
                "p50_ms": percentile(sample, 50) * 1e3,
                "p95_ms": percentile(sample, 95) * 1e3,
                "p99_ms": percentile(sample, 99) * 1e3,
                "max_ms": mx * 1e3,
            }
            nbytes = byte_totals.get(name)
            if nbytes:
                out[name]["bytes_total"] = nbytes
                if total > 0:
                    out[name]["gb_per_s"] = nbytes / total / 1e9
        return out

    def write_rollups(self, writer, step: int = 0) -> None:
        """Emit rollups through the MetricsWriter JSONL protocol — one
        ``event="span_rollup"`` record per span name plus one
        ``event="recompiles"`` record (compiles after warm-up, and the
        cumulative count per callable), all under ``split="trace"``."""
        for name, roll in self.rollups().items():
            writer.write(
                step, {"event": "span_rollup", "span": name, **roll},
                split="trace",
            )
        rec = {
            "event": "recompiles",
            "recompiles_total": sum(self.recompiles.values()),
        }
        for name, n in sorted(self.recompiles.items()):
            rec[f"recompiles.{name}"] = n
        with self._lock:
            compiles = dict(self._compiles)
        for name, n in sorted(compiles.items()):
            rec[f"compiles.{name}"] = n  # cumulative, warm-up included
        writer.write(step, rec, split="trace")

    # -- export ------------------------------------------------------------
    def export(self, path: Optional[str] = None) -> Optional[str]:
        """Write Chrome trace_event JSON, loadable in Perfetto and
        chrome://tracing. Default path: ``<trace_dir>/trace.json``."""
        if path is None:
            if self.trace_dir is None:
                return None
            path = os.path.join(self.trace_dir, "trace.json")
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with self._lock:
            events = list(self._events)
            dropped = self.dropped
            recompiles = dict(self.recompiles)
        doc = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "wall_start_unix_s": self._wall0,
                "pid": self._pid,
                "dropped_events": dropped,
                "recompiles": recompiles,
                "meta": dict(_meta),
            },
        }
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)  # a killed export never leaves a torn file
        return path


# -- module-level sites (the is-None fast path) ----------------------------
def span(name: str, **args):
    """Span context manager; shared no-op when tracing is disarmed."""
    t = _tracer
    if t is None:
        return _NULL_SPAN
    return _Span(t, name, args)


def instant(name: str, **args) -> None:
    t = _tracer
    if t is None:
        return
    t.instant(name, args or None)


def name_thread(name: str) -> None:
    """Name the calling thread's trace track; no-op when disarmed."""
    t = _tracer
    if t is None:
        return
    t.name_thread(name)


def counter(name: str, value) -> None:
    t = _tracer
    if t is None:
        return
    t.counter(name, value)


def note_compiles(name: str, n: Optional[int]) -> None:
    """Recompile-sentinel site; no-op when disarmed or ``n`` unknown."""
    t = _tracer
    if t is None or n is None:
        return
    t.note_compiles(name, int(n))


def active() -> bool:
    return _tracer is not None


def get() -> Optional[Tracer]:
    return _tracer


def configure(trace_dir: Optional[str] = None, **kw) -> Tracer:
    """Arm the process-wide tracer (replacing any active one)."""
    global _tracer
    _tracer = Tracer(trace_dir, **kw)
    return _tracer


def clear() -> None:
    """Disarm: every later site check is the single is-None test again."""
    global _tracer
    _tracer = None


@contextlib.contextmanager
def enabled(trace_dir: Optional[str] = None, **kw):
    """Scoped arming for tests; restores the previous tracer on exit."""
    global _tracer
    prev = _tracer
    t = configure(trace_dir, **kw)
    try:
        yield t
    finally:
        _tracer = prev


# -- goodput accounting ----------------------------------------------------
class GoodputAccount:
    """Wall-time classifier: productive / stalled / recovering / checkpoint.

    ``productive`` is compiled train/eval step execution (dispatch + the
    syncs that block on it); ``recovering`` is restore, stranded-
    checkpoint recovery, and resume batch replay; ``checkpoint`` is
    proactive save/swing time; ``stalled`` is watchdog-detected idle.
    Everything unattributed is reported as ``other_s`` (data wait,
    logging, python glue), so the buckets ALWAYS sum to wall:

        productive + stalled + recovering + checkpoint + other == wall_s

    ``goodput_pct`` — the headline number chaos drills track — is
    productive seconds over wall seconds since construction.
    """

    def __init__(self, clock=time.monotonic):
        self._clock = clock
        self.started_at = clock()
        self._lock = threading.Lock()
        self.buckets: Dict[str, float] = {}

    def add(self, bucket: str, seconds: float) -> None:
        if seconds <= 0:
            return
        with self._lock:
            self.buckets[bucket] = self.buckets.get(bucket, 0.0) + seconds

    def retract(self, bucket: str, seconds: float) -> None:
        """Withdraw seconds mistakenly attributed to ``bucket`` (clamped
        at its balance). The consumer is stall reclassification: a
        watchdog 'stall' that RESOLVES inside an attributed section was
        a slow op, not a hang — its wall time is already covered by the
        section's own add(), and leaving it in ``stalled`` too would
        break the buckets-sum-to-wall invariant."""
        if seconds <= 0:
            return
        with self._lock:
            cur = self.buckets.get(bucket, 0.0)
            self.buckets[bucket] = max(cur - seconds, 0.0)

    def wall_s(self) -> float:
        return max(self._clock() - self.started_at, 1e-9)

    def goodput_pct(self) -> float:
        return min(
            self.buckets.get("productive", 0.0) / self.wall_s(), 1.0
        ) * 100.0

    def summary(self) -> Dict[str, float]:
        with self._lock:
            buckets = dict(self.buckets)
        wall = self.wall_s()
        out: Dict[str, float] = {
            "wall_s": wall,
            "goodput_pct": min(
                buckets.get("productive", 0.0) / wall, 1.0
            ) * 100.0,
        }
        for b in sorted(set(GOODPUT_BUCKETS) | set(buckets)):
            out[f"{b}_s"] = buckets.get(b, 0.0)
        out["other_s"] = max(wall - sum(buckets.values()), 0.0)
        return out


def summarize_goodput(records, wall_s: Optional[float] = None) -> dict:
    """Aggregate ``split="goodput"`` MetricsWriter records — possibly
    several attempts of a killed/restarted run — into one account.

    ``wall_s`` overrides the denominator: a chaos drill passes its OWN
    wall clock (including restart gaps and killed attempts whose
    records never flushed), so the headline ``goodput_pct`` charges
    everything the drill lived through, not just what survived to disk.
    """
    g = [r for r in records if r.get("split") == "goodput"]
    out: Dict[str, Any] = {"attempts_recorded": len(g)}
    keys = set()
    for r in g:
        keys.update(k for k in r if k.endswith("_s"))
    for k in sorted(keys | {f"{b}_s" for b in GOODPUT_BUCKETS}
                    | {"other_s", "wall_s"}):
        out[k] = sum(float(r.get(k, 0.0)) for r in g)
    wall = wall_s if wall_s is not None else out.get("wall_s", 0.0)
    out["goodput_pct"] = (
        round(100.0 * out.get("productive_s", 0.0) / wall, 2)
        if wall > 0 else 0.0
    )
    if wall_s is not None:
        out["wall_s"] = wall_s
    return out
