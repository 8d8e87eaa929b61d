"""Always-on collective flight recorder + cross-rank hang autopsy core.

Distributed hangs are the one failure class the tracer cannot explain:
by the time a rank notices anything is wrong, the interesting history is
a deadline expiry (``rc=-110``) with zero cross-rank evidence, and the
tracer — armed only when someone asked for a trace — was almost
certainly off.  The flight recorder closes that gap the way the
reference stack's does: a **bounded, always-on** per-process ring buffer
of recent collective records that costs a few stores per operation and
is dumped to disk only when something goes wrong.

Record schema (one slot per collective/leg/transport call)::

    seq        monotonically increasing per-process record number
    kind       collective kind ("all_reduce", "all_gather", "barrier",
               "send", "recv", ...)
    op         reduce op / payload tag ("sum", "max", "-", ...)
    dtype      element dtype (stringified at dump time only)
    count      element count
    wire       algorithm wire bytes (hostring.algo_wire_bytes convention)
    transport  transport kind ("shm", "tcp", "hier", ...)
    group      group / segment name (rings are named per epoch+digest,
               hierarchy legs per tier — the autopsy aligns per group)
    state      ENQUEUED -> STARTED -> COMPLETED
    t0 / t1    time.monotonic() stamps at start / completion

Storage is **fixed-slot and preallocated**: numpy arrays for the numeric
columns, plain Python lists for the string columns (slot assignment of
an existing ``str`` object is a pointer store — no allocation, no dict
churn on the steady-state path).  This is why the recorder is exempt
from the PTD002 disarmed-cost discipline: there is no disarmed state —
recording IS the product, and its cost is pinned by bench.py's
``flightrec`` micro-phase.

Dumps are written as ``flight-rank<r>.json`` via tmp+``os.replace`` (the
ckpt_io atomicity discipline: a torn dump is a ``.tmp`` orphan, never a
half-written ``.json``), and embed :func:`tracing.get_meta` so the r6
clock-offset calibration travels with the records — the straggler
verdict needs it to compare start stamps across hosts.

Arming the dump path:

* ``PTD_FLIGHT_DUMP=<dir>`` in the environment configures the dump
  directory at import and installs a ``SIGTERM`` handler that dumps
  before dying (the elastic drills' kill path).
* :func:`configure` does the same programmatically and pins the rank
  (``PTD_FLIGHT_RANK`` is the env equivalent; membership stamps the
  committed view's rank on every re-mesh).
* With no directory configured, :func:`dump` is a no-op returning
  ``None`` — error paths all over the runtime call it unconditionally,
  and a test that provokes an ``rc`` failure must not leave files.

The autopsy half (:func:`load_dumps`, :func:`autopsy`) merges N dumps
and names the failure class; ``scripts/hang_autopsy.py`` is the CLI.
Verdict classes and detection envelopes are documented in
docs/DESIGN.md §24.

jax-free on purpose: imported by hostring/transport/membership workers
that never touch jax.
"""

from __future__ import annotations

import json
import logging
import os
import signal
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from pytorch_distributed_tpu.runtime import tracing

logger = logging.getLogger(__name__)

__all__ = [
    "FlightRecorder",
    "RECORDER",
    "configure",
    "dump",
    "last_completed_desc",
    "load_dumps",
    "autopsy",
    "DUMP_PREFIX",
    "DUMP_VERSION",
]

#: dump filename stem — ``flight-rank<r>.json`` (``.tmp`` while in flight)
DUMP_PREFIX = "flight-rank"

#: bumped when the record schema changes; the autopsy refuses mixtures
DUMP_VERSION = 1

# record states (int8 column; stringified only at dump time)
_ENQUEUED = 1
_STARTED = 2
_COMPLETED = 3

_STATE_NAMES = {_ENQUEUED: "enqueued", _STARTED: "started",
                _COMPLETED: "completed"}

_DEFAULT_CAPACITY = 4096


class FlightRecorder:
    """Bounded ring of collective records with fixed-slot storage.

    The hot path is three calls per collective — :meth:`begin`,
    :meth:`start`, :meth:`complete` — each a handful of array stores
    under a short lock (the lock serialises the comm thread's records
    with the main thread's; contention is nil because a rank's
    collectives are serial per group).  Nothing on the hot path
    allocates: the columns are preallocated at construction and slots
    are reused modulo capacity.
    """

    def __init__(self, capacity: int = _DEFAULT_CAPACITY):
        if capacity <= 0:
            raise ValueError(f"flight recorder capacity must be > 0, got {capacity}")
        self.capacity = int(capacity)
        n = self.capacity
        self._lock = threading.Lock()
        # numeric columns: preallocated, overwritten in place
        self._seq = np.full(n, -1, dtype=np.int64)
        self._state = np.zeros(n, dtype=np.int8)
        self._count = np.zeros(n, dtype=np.int64)
        self._wire = np.zeros(n, dtype=np.int64)
        self._t0 = np.zeros(n, dtype=np.float64)
        self._t1 = np.zeros(n, dtype=np.float64)
        # string columns: slot assignment of existing str objects only
        self._kind: List[Any] = [None] * n
        self._op: List[Any] = [None] * n
        self._dtype: List[Any] = [None] * n
        self._transport: List[Any] = [None] * n
        self._group: List[Any] = [None] * n
        self._next_seq = 0
        # O(1) last-completed summary for deadline error messages
        self._last_done_seq = -1
        self._last_done_kind: Optional[str] = None
        self._last_done_op: Optional[str] = None

    # ---------------------------------------------------------------- hot path

    def begin(self, kind: str, op: str, dtype: Any, count: int,
              wire_bytes: int, transport: str, group: str) -> int:
        """Claim the next slot as ENQUEUED; returns the record's seq."""
        with self._lock:
            seq = self._next_seq
            self._next_seq = seq + 1
            i = seq % self.capacity
            self._seq[i] = seq
            self._state[i] = _ENQUEUED
            self._count[i] = count
            self._wire[i] = wire_bytes
            self._t0[i] = 0.0
            self._t1[i] = 0.0
            self._kind[i] = kind
            self._op[i] = op
            self._dtype[i] = dtype
            self._transport[i] = transport
            self._group[i] = group
        return seq

    def start(self, seq: int) -> None:
        """Mark seq STARTED and stamp t0 (immediately before the wire call)."""
        i = seq % self.capacity
        # no lock: the slot is owned by this seq until capacity more
        # records are begun, and a stale overwrite after wrap is benign
        if self._seq[i] == seq:
            self._t0[i] = time.monotonic()
            self._state[i] = _STARTED

    def complete(self, seq: int) -> None:
        """Mark seq COMPLETED and stamp t1 (after the wire call returns)."""
        i = seq % self.capacity
        if self._seq[i] == seq:
            self._t1[i] = time.monotonic()
            self._state[i] = _COMPLETED
            self._last_done_seq = seq
            self._last_done_kind = self._kind[i]
            self._last_done_op = self._op[i]

    # ------------------------------------------------------------- cold paths

    def last_completed(self) -> Optional[Tuple[int, str, str]]:
        """``(seq, kind, op)`` of the newest completed record, or None."""
        if self._last_done_seq < 0:
            return None
        return (self._last_done_seq, self._last_done_kind, self._last_done_op)

    def records(self) -> List[Dict[str, Any]]:
        """Snapshot of live records, oldest first (cold path: dumps/tests)."""
        with self._lock:
            end = self._next_seq
            start = max(0, end - self.capacity)
            out = []
            for seq in range(start, end):
                i = seq % self.capacity
                if self._seq[i] != seq:  # overwritten mid-snapshot
                    continue
                out.append({
                    "seq": int(seq),
                    "kind": self._kind[i],
                    "op": self._op[i],
                    "dtype": str(self._dtype[i]),
                    "count": int(self._count[i]),
                    "wire_bytes": int(self._wire[i]),
                    "transport": self._transport[i],
                    "group": self._group[i],
                    "state": _STATE_NAMES.get(int(self._state[i]), "?"),
                    "t0_mono_s": float(self._t0[i]),
                    "t1_mono_s": float(self._t1[i]),
                })
            return out


#: the process-wide always-on recorder (capacity override:
#: ``PTD_FLIGHT_SLOTS`` — tests shrink it to prove wraparound)
RECORDER = FlightRecorder(int(os.environ.get("PTD_FLIGHT_SLOTS", _DEFAULT_CAPACITY)))

# dump configuration: directory None == dumps disabled (the default, so
# the unconditional dump() calls on runtime error paths stay inert in
# every test that provokes an rc failure on purpose)
_dump_dir: Optional[str] = None
_rank: Optional[int] = None
_world: Optional[int] = None
_dump_lock = threading.Lock()


def configure(out_dir: Optional[str] = None, rank: Optional[int] = None,
              world: Optional[int] = None) -> None:
    """Arm (or re-point) the dump path; each argument is sticky if None."""
    global _dump_dir, _rank, _world
    if out_dir is not None:
        _dump_dir = str(out_dir)
    if rank is not None:
        _rank = int(rank)
    if world is not None:
        _world = int(world)


def _resolved_rank() -> int:
    if _rank is not None:
        return _rank
    env = os.environ.get("PTD_FLIGHT_RANK")
    if env is not None:
        try:
            return int(env)
        except ValueError:
            pass
    meta = tracing.get_meta()
    try:
        return int(meta.get("rank", 0))
    except (TypeError, ValueError):
        return 0


def _resolved_world() -> Optional[int]:
    if _world is not None:
        return _world
    meta = tracing.get_meta()
    w = meta.get("world_size")
    return int(w) if w is not None else None


def _opname(kind: str, op: str) -> str:
    """``all_reduce/sum`` but bare ``barrier`` — kinds with no reduce op
    carry ``op=""`` (the ``_comm_span`` convention); don't render the
    dangling slash."""
    return f"{kind}/{op}" if op else kind


def last_completed_desc() -> str:
    """One clause for deadline error messages: where this rank stopped."""
    last = RECORDER.last_completed()
    if last is None:
        return "no collective completed yet"
    seq, kind, op = last
    return f"last completed flight seq={seq} {_opname(kind, op)}"


def dump(reason: str, out_dir: Optional[str] = None) -> Optional[str]:
    """Write ``flight-rank<r>.json`` atomically; no-op if unconfigured.

    Returns the written path, or None when no dump directory is armed.
    Never raises: the dump sits on error paths that must still deliver
    their original exception.
    """
    d = out_dir if out_dir is not None else _dump_dir
    if d is None:
        return None
    try:
        rank = _resolved_rank()
        payload = {
            "version": DUMP_VERSION,
            "rank": rank,
            "world_size": _resolved_world(),
            "reason": reason,
            # paired wall/monotonic stamps let the autopsy map each
            # rank's monotonic record stamps onto shared wall time
            "wall_unix_s": time.time(),
            "monotonic_s": time.monotonic(),
            "meta": tracing.get_meta(),
            "records": RECORDER.records(),
        }
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, f"{DUMP_PREFIX}{rank}.json")
        tmp = path + ".tmp"
        with _dump_lock:
            with open(tmp, "w") as f:
                json.dump(payload, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        logger.warning("flight recorder dumped %d records to %s (%s)",
                       len(payload["records"]), path, reason)
        return path
    except Exception as e:  # pragma: no cover - defensive
        logger.warning("flight recorder dump failed: %s", e)
        return None


def _sigterm_dump(signum, frame):  # pragma: no cover - exercised in subprocess
    dump(f"signal {signum}")
    signal.signal(signum, signal.SIG_DFL)
    os.kill(os.getpid(), signum)


def _install_from_env() -> None:
    """Arm dumps from ``PTD_FLIGHT_DUMP`` / ``PTD_FLIGHT_RANK`` at import."""
    d = os.environ.get("PTD_FLIGHT_DUMP")
    if not d:
        return
    configure(out_dir=d)
    if threading.current_thread() is threading.main_thread():
        try:
            signal.signal(signal.SIGTERM, _sigterm_dump)
        except (ValueError, OSError):  # non-main interpreter contexts
            pass


_install_from_env()


# --------------------------------------------------------------------------
# autopsy: merge N dumps, align per group, name the failure class
# --------------------------------------------------------------------------

#: start-stamp skew (seconds) beyond which matched records are called a
#: straggler, on top of the r6 clock-offset error budget when present
STRAGGLER_BUDGET_S = 1.0


def load_dumps(dump_dir: str, strict: bool = False) -> Dict[int, Dict[str, Any]]:
    """Read every ``flight-rank*.json`` under ``dump_dir``.

    Returns ``{rank: payload}``.  A ``.tmp`` orphan (SIGKILL mid-dump)
    or a torn/unparseable file is skipped with a warning — the
    ``read_metrics`` torn-line discipline — unless ``strict=True``,
    which restores the raise.  Two dumps claiming the same rank are
    refused loudly (the trace_merge duplicate-rank idiom): a merged
    verdict over ambiguous evidence would be worse than none.
    """
    out: Dict[int, Dict[str, Any]] = {}
    sources: Dict[int, str] = {}
    for name in sorted(os.listdir(dump_dir)):
        if not name.startswith(DUMP_PREFIX):
            continue
        path = os.path.join(dump_dir, name)
        if name.endswith(".tmp"):
            msg = f"skipping torn flight dump {path} (writer died mid-dump)"
            if strict:
                raise ValueError(msg)
            logger.warning(msg)
            continue
        if not name.endswith(".json"):
            continue
        try:
            with open(path) as f:
                payload = json.load(f)
            rank = int(payload["rank"])
            if int(payload.get("version", -1)) != DUMP_VERSION:
                raise ValueError(f"unsupported dump version {payload.get('version')}")
        except (ValueError, KeyError, TypeError, json.JSONDecodeError) as e:
            if strict:
                raise ValueError(f"torn or invalid flight dump {path}: {e}") from e
            logger.warning("skipping torn or invalid flight dump %s: %s", path, e)
            continue
        if rank in out:
            raise ValueError(
                f"duplicate flight dumps for rank {rank}: {sources[rank]} and "
                f"{path} — refusing to merge ambiguous evidence (remove one)")
        out[rank] = payload
        sources[rank] = path
    return out


def _per_group_streams(payload: Dict[str, Any]) -> Dict[str, List[Dict[str, Any]]]:
    streams: Dict[str, List[Dict[str, Any]]] = {}
    for rec in payload.get("records", ()):
        streams.setdefault(rec["group"], []).append(rec)
    return streams


def _clock_budget_s(dumps: Dict[int, Dict[str, Any]]) -> float:
    """Straggler threshold: base budget + the widest r6 offset spread."""
    spread = 0.0
    for p in dumps.values():
        offs = p.get("meta", {}).get("clock_offsets_s")
        if offs:
            try:
                spread = max(spread, max(offs) - min(offs))
            except (TypeError, ValueError):
                pass
    return STRAGGLER_BUDGET_S + spread


def autopsy(dumps: Dict[int, Dict[str, Any]]) -> Dict[str, Any]:
    """Merge per-rank dumps into a verdict naming the failure class.

    Alignment is per group, by occurrence index: every rank calls a
    given group's collectives in lockstep program order (the PTD001
    discipline), so the i-th record a rank logged against group G is
    the same logical operation as every other rank's i-th record for G.
    The verdict is decided at the first divergence found:

    * ``missing_rank`` — some rank's stream ends (or the rank left no
      dump at all) while peers show the next operation ``started``;
      the victim is the silent rank.
    * ``mismatch`` — same occurrence index, different kind/op/count:
      the PTD001 violation class post-mortem; the victim is the
      minority side.
    * ``straggler`` — streams agree but one rank's start stamps trail
      its peers beyond the clock-offset error budget.
    * ``inconclusive`` — nothing above holds (e.g. a single dump, or a
      rank that died before its first collective and left no log).

    Returns ``{"verdict", "victim_rank", "seq", "op", "group",
    "evidence", "detail"}`` — ``evidence`` is a per-rank table of rows
    ``{rank, seq, kind, op, count, state}`` at the deciding index.
    """
    if not dumps:
        return {"verdict": "inconclusive", "victim_rank": None, "seq": None,
                "op": None, "group": None, "evidence": [],
                "detail": "no flight dumps found"}

    world = None
    for p in dumps.values():
        if p.get("world_size"):
            world = max(world or 0, int(p["world_size"]))
    if world is None:
        world = max(dumps) + 1

    streams = {r: _per_group_streams(p) for r, p in dumps.items()}
    groups = sorted({g for s in streams.values() for g in s})
    budget = _clock_budget_s(dumps)

    def row(rank, rec):
        if rec is None:
            return {"rank": rank, "seq": None, "kind": None, "op": None,
                    "count": None, "state": "absent"}
        return {"rank": rank, "seq": rec["seq"], "kind": rec["kind"],
                "op": rec["op"], "count": rec["count"], "state": rec["state"]}

    # pass 1: a rank with no dump at all, while some peer is stuck
    # started — classic SIGKILLed/desynced victim that never dumped
    absent = sorted(set(range(world)) - set(dumps))
    straggler_hit: Optional[Dict[str, Any]] = None

    for g in groups:
        ranks = sorted(r for r in streams if g in streams[r])
        if len(ranks) < 2 and not absent:
            continue
        per = {r: streams[r][g] for r in ranks}
        depth = max(len(s) for s in per.values())
        for i in range(depth):
            recs = {r: (per[r][i] if i < len(per[r]) else None) for r in ranks}
            live = {r: rec for r, rec in recs.items() if rec is not None}
            if not live:
                continue
            # mismatch: same occurrence index, different op signature
            sigs = {(rec["kind"], rec["op"], rec["count"]) for rec in live.values()}
            if len(sigs) > 1:
                by_sig: Dict[Tuple, List[int]] = {}
                for r, rec in live.items():
                    by_sig.setdefault((rec["kind"], rec["op"], rec["count"]), []).append(r)
                minority = min(by_sig.values(), key=len)
                victim = minority[0]
                vrec = live[victim]
                return {
                    "verdict": "mismatch", "victim_rank": victim,
                    "seq": vrec["seq"], "op": _opname(vrec["kind"], vrec["op"]),
                    "group": g,
                    "evidence": [row(r, recs[r]) for r in ranks],
                    "detail": (f"occurrence {i} of group {g}: rank {victim} "
                               f"issued {_opname(vrec['kind'], vrec['op'])} "
                               f"count={vrec['count']} against "
                               f"{len(live) - len(minority)} peers on a "
                               "different signature (PTD001 violation class)"),
                }
            # missing: someone's stream ran out while a peer is stuck
            exhausted = [r for r, rec in recs.items() if rec is None]
            stuck = [r for r, rec in live.items() if rec["state"] != "completed"]
            if exhausted and stuck:
                victim = exhausted[0]
                ref = live[stuck[0]]
                return {
                    "verdict": "missing_rank", "victim_rank": victim,
                    "seq": ref["seq"], "op": _opname(ref["kind"], ref["op"]),
                    "group": g,
                    "evidence": [row(r, recs[r]) for r in ranks],
                    "detail": (f"occurrence {i} of group {g}: peers show "
                               f"{_opname(ref['kind'], ref['op'])} "
                               f"{ref['state']}, rank {victim}'s log ends at "
                               f"occurrence {i - 1}"),
                }
            # straggler candidate: matched records, skewed start stamps
            done = {r: rec for r, rec in live.items()
                    if rec["state"] == "completed" and rec["t0_mono_s"] > 0.0}
            if straggler_hit is None and len(done) >= 2:
                starts = {r: _wall_start(dumps[r], rec) for r, rec in done.items()}
                late = max(starts, key=starts.get)
                skew = starts[late] - min(starts.values())
                if skew > budget:
                    vrec = done[late]
                    straggler_hit = {
                        "verdict": "straggler", "victim_rank": late,
                        "seq": vrec["seq"], "op": _opname(vrec["kind"], vrec["op"]),
                        "group": g,
                        "evidence": [row(r, recs[r]) for r in ranks],
                        "detail": (f"occurrence {i} of group {g}: rank {late} "
                                   f"started {skew:.3f}s after the earliest "
                                   f"peer (budget {budget:.3f}s incl. clock "
                                   "offsets)"),
                    }

    # no in-dump divergence: an absent rank next to a stuck peer still
    # names a victim (the rank that left no log at all)
    if absent:
        for g in groups:
            ranks = sorted(r for r in streams if g in streams[r])
            for r in ranks:
                stream = streams[r][g]
                if stream and stream[-1]["state"] != "completed":
                    ref = stream[-1]
                    return {
                        "verdict": "missing_rank", "victim_rank": absent[0],
                        "seq": ref["seq"], "op": _opname(ref["kind"], ref["op"]),
                        "group": g,
                        "evidence": ([row(r2, streams[r2][g][-1]) for r2 in ranks]
                                     + [row(a, None) for a in absent]),
                        "detail": (f"rank(s) {absent} left no dump; rank {r} is "
                                   f"stuck {ref['state']} in "
                                   f"{_opname(ref['kind'], ref['op'])} of "
                                   f"group {g} — a rank that "
                                   "never reached its first collective (or was "
                                   "SIGKILLed before dumping) leaves no log"),
                    }

    if straggler_hit is not None:
        return straggler_hit

    return {"verdict": "inconclusive", "victim_rank": None, "seq": None,
            "op": None, "group": None, "evidence": [],
            "detail": (f"{len(dumps)} dump(s), no op divergence, no stuck "
                       "record with a silent peer — the world may have died "
                       "outside a collective")}


def _wall_start(payload: Dict[str, Any], rec: Dict[str, Any]) -> float:
    """Map a record's monotonic start stamp onto shared wall time."""
    base_wall = payload.get("wall_unix_s", 0.0)
    base_mono = payload.get("monotonic_s", 0.0)
    wall = base_wall + (rec["t0_mono_s"] - base_mono)
    # r6 calibration: offset of this rank's wall clock vs rank 0's
    off = payload.get("meta", {}).get("clock_offset_s")
    if isinstance(off, (int, float)):
        wall -= off
    return wall
