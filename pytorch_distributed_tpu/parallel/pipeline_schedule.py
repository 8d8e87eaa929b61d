"""Host-dispatched pipeline-parallel schedules: 1F1B over the hostring (r20).

The SPMD GPipe sketch (``parallel/pipeline.py``) runs every stage inside ONE
jitted program: each of the ``M + S - 1`` ppermute ticks makes *every* stage
compute, so the warm-up/cool-down bubble — an ``(S-1)/(M+S-1)`` fraction of
the ticks — is paid in real FLOPs on garbage microbatches. This module is the
host-dispatched alternative in the ``HostLoopStep`` discipline: each rank owns
ONE stage, compiles its forward and backward once each, and a host loop issues
the ops of a precomputed schedule, linking neighbor stages with
``hostring.send/recv`` activation/grad handoffs tagged by
``(microbatch, stage, direction)`` through the DETAIL fingerprint handshake.

Two schedule shapes, both pure functions of ``(stage, S, M)``:

* ``schedule_gpipe`` — all ``M`` forwards, then all ``M`` backwards. Simple,
  but every stage must hold all ``M`` in-flight microbatch inputs at the
  fwd/bwd boundary (``peak_live_microbatches == M``).
* ``schedule_1f1b`` — ``min(S-1-stage, M)`` warm-up forwards, then the 1F1B
  steady state (one forward, one backward, alternating), then the cool-down
  backwards. At most ``min(S - stage, M)`` microbatches are ever live per
  stage — bounded by ``S`` regardless of ``M``: the memory win over GPipe.
  Wall-clock is the same ``(M + S - 1)`` tick critical path as an honest
  host GPipe; the bubble fraction both pay is the analytic
  ``(S-1)/(M+S-1)`` (``bubble_fraction``), which ``autoplan/pricing.py``
  charges when ranking pp candidates.

Because the issue order is a pure function of ``(stage, S, M)``, lockstep is
by construction: there is no rank-conditional branch around a send/recv for
ptdlint's PTD001 to distrust — the executor walks the op list and dispatches
on ``op.kind`` (see ``tests/lint_fixtures/ptd001_pipeline_good.py``).

Interleaved virtual stages (``schedule_interleaved``) shrink the bubble to
``(S-1)/(V*M + S-1)`` by giving each rank ``V`` non-contiguous layer chunks;
the schedule/mapping math ships tested here, the executor runs ``V == 1``
(honest limits in docs/DESIGN.md §25).

Deadlock discipline: the shm transport's P2P mailboxes buffer ONE in-flight
message per ordered rank pair (native/hostring.cpp), and activations
(``s -> s+1``) and grads (``s+1 -> s``) ride *different* ordered pairs.
``simulate_links`` replays any schedule set against exactly that channel
model; the (S, M) grid test pins that both shapes drain without deadlock and
without tag reordering.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# -- op kinds (strings, not an enum: they appear in fault paths and traces) --
RECV_ACT = "recv_act"
FWD = "fwd"
SEND_ACT = "send_act"
RECV_GRAD = "recv_grad"
BWD = "bwd"
SEND_GRAD = "send_grad"

COMPUTE_KINDS = (FWD, BWD)
COMM_KINDS = (RECV_ACT, SEND_ACT, RECV_GRAD, SEND_GRAD)


@dataclass(frozen=True)
class StageOp:
    """One schedule slot: ``kind`` over microbatch ``mb`` (chunk = the
    virtual-stage index on this rank; 0 unless interleaved)."""

    kind: str
    mb: int
    chunk: int = 0


def _check_args(stage: int, num_stages: int, num_microbatches: int) -> None:
    if num_stages < 1:
        raise ValueError(f"num_stages must be >= 1, got {num_stages}")
    if not 0 <= stage < num_stages:
        raise ValueError(f"stage {stage} outside [0, {num_stages})")
    if num_microbatches < 1:
        raise ValueError(
            f"num_microbatches must be >= 1, got {num_microbatches}"
        )


def _attach_comms(
    skeleton: Sequence[Tuple[str, int]], stage: int, num_stages: int
) -> Tuple[StageOp, ...]:
    """Wrap a (kind, mb) compute skeleton with the neighbor handoffs: a
    non-first stage receives its input activation just-in-time before each
    forward; a non-last stage sends the activation right after, receives the
    output grad just-in-time before each backward; a non-first stage sends
    the input grad right after."""
    first = stage == 0
    last = stage == num_stages - 1
    ops: List[StageOp] = []
    for kind, mb in skeleton:
        if kind == FWD:
            if not first:
                ops.append(StageOp(RECV_ACT, mb))
            ops.append(StageOp(FWD, mb))
            if not last:
                ops.append(StageOp(SEND_ACT, mb))
        else:
            if not last:
                ops.append(StageOp(RECV_GRAD, mb))
            ops.append(StageOp(BWD, mb))
            if not first:
                ops.append(StageOp(SEND_GRAD, mb))
    return tuple(ops)


def schedule_1f1b(
    stage: int, num_stages: int, num_microbatches: int
) -> Tuple[StageOp, ...]:
    """The 1F1B op list for ``stage``: warm-up ``min(S-1-stage, M)``
    forwards, steady-state (fwd, bwd) pairs, cool-down backwards.

    Pure function of ``(stage, num_stages, num_microbatches)`` — the
    lockstep-by-construction property every caller leans on. Backwards
    complete in increasing microbatch order, so a left fold over them is
    the same association ``lax.scan``'s accumulation uses.
    """
    _check_args(stage, num_stages, num_microbatches)
    warmup = min(num_stages - 1 - stage, num_microbatches)
    skeleton: List[Tuple[str, int]] = []
    f = b = 0
    for _ in range(warmup):
        skeleton.append((FWD, f))
        f += 1
    for _ in range(num_microbatches - warmup):
        skeleton.append((FWD, f))
        f += 1
        skeleton.append((BWD, b))
        b += 1
    for _ in range(warmup):
        skeleton.append((BWD, b))
        b += 1
    return _attach_comms(skeleton, stage, num_stages)


def schedule_gpipe(
    stage: int, num_stages: int, num_microbatches: int
) -> Tuple[StageOp, ...]:
    """The host GPipe op list: all forwards, then all backwards. Same
    ``(M + S - 1)``-tick critical path as 1F1B, but the stage must hold all
    ``M`` microbatch inputs at the fwd/bwd boundary — the memory cost
    ``schedule_1f1b`` exists to avoid."""
    _check_args(stage, num_stages, num_microbatches)
    skeleton = [(FWD, i) for i in range(num_microbatches)]
    skeleton += [(BWD, i) for i in range(num_microbatches)]
    return _attach_comms(skeleton, stage, num_stages)


def virtual_stage(rank: int, chunk: int, world: int) -> int:
    """Global stage id of ``chunk`` on ``rank`` under interleaving: chunk
    ``v`` of rank ``r`` runs global stage ``v * world + r`` — consecutive
    global stages land on consecutive ranks, so every chunk boundary is a
    one-hop neighbor handoff."""
    return chunk * world + rank


def schedule_interleaved(
    rank: int, world: int, num_chunks: int, num_microbatches: int
) -> Tuple[StageOp, ...]:
    """Interleaved-virtual-stage 1F1B (Megatron-style): each rank runs
    ``num_chunks`` layer chunks, microbatches advance in groups of
    ``world``, and the warm-up is deep enough to keep every chunk fed.

    Compute ops only (``chunk`` = local chunk index; the global stage is
    ``virtual_stage(rank, chunk, world)``) — this is the schedule/mapping
    math the planner prices and the tests pin; the executor runs V == 1.
    Requires ``num_microbatches % world == 0`` (the grouping invariant).
    """
    if world < 1 or not 0 <= rank < world:
        raise ValueError(f"rank {rank} outside [0, {world})")
    if num_chunks < 2:
        raise ValueError(
            "interleaving needs num_chunks >= 2 — V == 1 is plain 1F1B "
            "(schedule_1f1b)"
        )
    if num_microbatches % world:
        raise ValueError(
            f"interleaved schedule needs num_microbatches divisible by "
            f"world, got M={num_microbatches} world={world}"
        )
    total = num_microbatches * num_chunks
    warmup = min((world - rank - 1) * 2 + (num_chunks - 1) * world, total)

    def fwd_op(k: int) -> StageOp:
        chunk = (k // world) % num_chunks
        mb = (k // (world * num_chunks)) * world + k % world
        return StageOp(FWD, mb, chunk)

    def bwd_op(k: int) -> StageOp:
        chunk = num_chunks - 1 - (k // world) % num_chunks
        mb = (k // (world * num_chunks)) * world + k % world
        return StageOp(BWD, mb, chunk)

    ops = [fwd_op(k) for k in range(warmup)]
    for k in range(warmup, total):
        ops.append(fwd_op(k))
        ops.append(bwd_op(k - warmup))
    for k in range(total - warmup, total):
        ops.append(bwd_op(k))
    return tuple(ops)


def bubble_fraction(
    num_stages: int, num_microbatches: int, num_chunks: int = 1
) -> float:
    """The analytic pipeline bubble: the fraction of the steady-state
    critical path spent waiting for the pipe to fill and drain —
    ``(S-1) / (V*M + S-1)``. This is the price ``autoplan/pricing.py``
    multiplies into a pp candidate's compute seconds."""
    if num_stages < 1 or num_microbatches < 1 or num_chunks < 1:
        raise ValueError(
            f"need S, M, V >= 1, got ({num_stages}, {num_microbatches}, "
            f"{num_chunks})"
        )
    return (num_stages - 1) / (
        num_chunks * num_microbatches + num_stages - 1
    )


def peak_live_microbatches(program: Sequence[StageOp]) -> int:
    """Max concurrently-live microbatches implied by a schedule: a forward
    stashes its input until the matching backward retires it. For 1F1B
    stage ``s`` this is ``min(S - s, M)`` (<= S everywhere); for GPipe it
    is ``M`` at every stage — the accounting behind the memory claim."""
    live = peak = 0
    for op in program:
        if op.kind == FWD:
            live += 1
            peak = max(peak, live)
        elif op.kind == BWD:
            live -= 1
    return peak


def stage_depths(
    num_layers: int,
    num_stages: int,
    rank_rates: Optional[Sequence[float]] = None,
) -> Tuple[int, ...]:
    """Layers per stage. Even split when ``rank_rates`` is None (requires
    divisibility — refusing beats silently unbalancing a homogeneous
    fleet); with per-rank rates, the ``train/balance.py`` apportionment
    gives a slow rank a proportionally shallower stage (floor 1 layer)."""
    if num_stages < 1:
        raise ValueError(f"num_stages must be >= 1, got {num_stages}")
    if num_layers < num_stages:
        raise ValueError(
            f"{num_layers} layers cannot fill {num_stages} stages"
        )
    if rank_rates is None:
        if num_layers % num_stages:
            raise ValueError(
                f"{num_layers} layers not divisible by {num_stages} "
                "stages — pass rank_rates to apportion unevenly"
            )
        return (num_layers // num_stages,) * num_stages
    if len(rank_rates) != num_stages:
        raise ValueError(
            f"{len(rank_rates)} rates for {num_stages} stages"
        )
    from pytorch_distributed_tpu.train.balance import (
        apportion,
        quantize_rates,
    )

    return tuple(apportion(num_layers, quantize_rates(rank_rates), floor=1))


def stage_layer_slices(
    depths: Sequence[int],
) -> Tuple[Tuple[int, int], ...]:
    """(start, stop) layer ranges per stage for a depth list."""
    out, start = [], 0
    for d in depths:
        out.append((start, start + d))
        start += d
    return tuple(out)


class ScheduleDeadlock(RuntimeError):
    """Raised by :func:`simulate_links` when no stage can advance."""


def simulate_links(
    programs: Sequence[Sequence[StageOp]], capacity: int = 1
) -> int:
    """Replay per-stage op lists against the shm transport's channel model
    (one mailbox per ordered rank pair, ``capacity`` buffered messages —
    native/hostring.cpp buffers exactly one) and return the number of
    round-robin passes to drain. Raises :class:`ScheduleDeadlock` if every
    stage blocks, and ValueError if a receive would consume a message out
    of tag order — the static form of the DETAIL fingerprint mismatch."""
    num_stages = len(programs)
    pcs = [0] * num_stages
    chans: Dict[Tuple[int, int], List[Tuple[str, int]]] = {}
    passes = 0
    while any(pc < len(programs[s]) for s, pc in enumerate(pcs)):
        progressed = False
        passes += 1
        for s in range(num_stages):
            if pcs[s] >= len(programs[s]):
                continue
            op = programs[s][pcs[s]]
            if op.kind in COMPUTE_KINDS:
                pcs[s] += 1
                progressed = True
                continue
            direction = "act" if op.kind in (RECV_ACT, SEND_ACT) else "grad"
            if op.kind == SEND_ACT:
                pair = (s, s + 1)
            elif op.kind == SEND_GRAD:
                pair = (s, s - 1)
            elif op.kind == RECV_ACT:
                pair = (s - 1, s)
            else:
                pair = (s + 1, s)
            chan = chans.setdefault(pair, [])
            if op.kind in (SEND_ACT, SEND_GRAD):
                if len(chan) < capacity:
                    chan.append((direction, op.mb))
                    pcs[s] += 1
                    progressed = True
            else:
                if chan:
                    if chan[0] != (direction, op.mb):
                        raise ValueError(
                            f"stage {s} expects {direction}.m{op.mb} but "
                            f"channel {pair} holds {chan[0]} — schedule "
                            "would trip the fingerprint handshake"
                        )
                    chan.pop(0)
                    pcs[s] += 1
                    progressed = True
        if not progressed:
            stuck = {
                s: str(programs[s][pc])
                for s, pc in enumerate(pcs) if pc < len(programs[s])
            }
            raise ScheduleDeadlock(
                f"no stage can advance after {passes} passes: {stuck}"
            )
    return passes


def pipeline_trace_stats(
    events: Sequence[dict],
) -> Dict[int, Dict[str, float]]:
    """Per-rank busy/bubble/link accounting from merged chrome-trace
    events (``scripts/trace_merge.py`` output: ``pid`` = rank, us).

    For each rank with ``pipeline.fwd``/``pipeline.bwd`` spans: ``busy_s``
    is their summed duration, ``window_s`` the first-start to last-end
    extent, ``bubble`` the idle fraction ``1 - busy/window``, and
    ``link_s`` the summed ``comm.send``/``comm.recv`` span time inside the
    window — all exposed on the serial host loop, so ``link_s/window_s``
    IS the exposed-link ratio the bench pins."""
    by_rank: Dict[int, Dict[str, float]] = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        name = str(ev.get("name", ""))
        if name in ("pipeline.fwd", "pipeline.bwd"):
            key = "busy"
        elif name in ("comm.send", "comm.recv"):
            key = "link"
        else:
            continue
        rank = int(ev.get("pid", 0))
        rec = by_rank.setdefault(
            rank, {"busy": 0.0, "link": 0.0, "t0": float("inf"), "t1": 0.0}
        )
        rec[key] += float(ev.get("dur", 0.0))
        if key == "busy":
            rec["t0"] = min(rec["t0"], float(ev["ts"]))
            rec["t1"] = max(rec["t1"], float(ev["ts"]) + float(ev["dur"]))
    out: Dict[int, Dict[str, float]] = {}
    for rank, rec in sorted(by_rank.items()):
        window = max(rec["t1"] - rec["t0"], 1e-9)
        out[rank] = {
            "busy_s": rec["busy"] / 1e6,
            "link_s": rec["link"] / 1e6,
            "window_s": window / 1e6,
            "bubble": max(0.0, 1.0 - rec["busy"] / window),
        }
    return out


class HostPipelineStep:
    """Host-dispatched pipeline stage executor: one rank, one stage, one
    fwd and one bwd program compiled once each (the ``HostLoopStep``
    prep/grad/apply idiom applied to a stage), activations and grads
    linked over ``hostring.send/recv`` with ``(microbatch, stage,
    direction)`` tags through the DETAIL fingerprint handshake.

    ``programs`` supplies the per-stage math (``parallel/pipeline_lm.py``
    builds the GPT-2 bridge):

    * non-last stages: ``fwd(params, xin) -> y`` and
      ``bwd(params, xin, dy) -> (grads, dx)`` (first stage:
      ``bwd(params, ids_mb, dy) -> grads`` — integer inputs have no dx);
      the backward re-derives the forward via ``jax.vjp`` inside the jit,
      so only the stage INPUT is stashed per live microbatch — the
      ``peak_live_microbatches`` accounting is exactly the executor's
      stash size.
    * last stage (S > 1): ``loss_grad(params, head_wte, x, ids_mb) ->
      (loss, grads, head_grad, dx)``; S == 1:
      ``loss_grad_solo(params, ids_mb) -> (loss, grads)``.
    * optional ``exchange_grads(group, stage, num_stages, grads,
      aux_grad)`` / ``exchange_params(group, stage, num_stages, params,
      buffers)`` hooks for tied weights (the GPT-2 bridge pairs the
      first/last wte replicas over tagged P2P).

    Grads are left-folded in microbatch order (1F1B backwards complete in
    increasing mb order, so this is ``lax.scan``'s association) and scaled
    by ``1/M`` inside the jitted ``apply`` — the exact-multiply step.
    Cross-stage reductions inside the optimizer (global-norm clipping) are
    out of scope: ``tx`` must be elementwise per stage (DESIGN.md §25).

    ``delay_s`` sleeps that long before each compute op, OUTSIDE the
    math: a 1-core box then behaves like an S-deep pipeline because
    sleeps overlap across processes — the bench's
    bubble-measurement shaping, with bit-identity to the delay-free run
    enforced by CRC.
    """

    def __init__(
        self,
        programs,
        *,
        stage: int,
        num_stages: int,
        num_microbatches: int,
        tx,
        group=None,
        schedule: str = "1f1b",
        act_template: Optional[np.ndarray] = None,
        delay_s: float = 0.0,
        ids_key: str = "input_ids",
    ):
        import jax

        if schedule == "1f1b":
            self.program = schedule_1f1b(stage, num_stages, num_microbatches)
        elif schedule == "gpipe":
            self.program = schedule_gpipe(
                stage, num_stages, num_microbatches
            )
        else:
            raise ValueError(
                f"schedule must be '1f1b' or 'gpipe', got {schedule!r}"
            )
        if num_stages > 1 and group is None:
            raise ValueError("num_stages > 1 needs a hostring group")
        if num_stages > 1 and act_template is None:
            raise ValueError("num_stages > 1 needs an act_template buffer")
        self.stage = stage
        self.num_stages = num_stages
        self.num_microbatches = num_microbatches
        self.group = group
        self.delay_s = float(delay_s)
        self.ids_key = ids_key
        self.programs = programs
        self._first = stage == 0
        self._last = stage == num_stages - 1
        self._act_buf = (
            None if act_template is None
            else np.ascontiguousarray(act_template)
        )
        # fault paths precomputed so the armed-site poll stays a Name arg
        self._paths = tuple(
            f"s{stage}.{op.kind}.m{op.mb}" for op in self.program
        )
        self._tx = tx
        inv = 1.0 / num_microbatches

        def apply_fn(params, opt_state, grads):
            g = jax.tree_util.tree_map(lambda a: a * inv, grads)
            updates, new_opt = tx.update(g, opt_state, params)
            import optax

            return optax.apply_updates(params, updates), new_opt

        self._jits: Dict[str, object] = {"apply": jax.jit(apply_fn)}
        if num_stages == 1:
            self._jits["loss_grad"] = jax.jit(programs.loss_grad_solo)
        elif self._last:
            self._jits["loss_grad"] = jax.jit(programs.loss_grad)
        else:
            self._jits["fwd"] = jax.jit(programs.fwd)
            self._jits["bwd"] = jax.jit(programs.bwd)

    def compile_counts(self) -> Dict[str, Optional[int]]:
        """Jit-cache sizes per program — the pin is 1 per program per
        distinct microbatch shape (the compile-count correctness bar)."""
        from pytorch_distributed_tpu.runtime.compat import jit_cache_size

        return {k: jit_cache_size(v) for k, v in sorted(self._jits.items())}

    def init_opt_state(self, params):
        return self._tx.init(params)

    # -- internals ----------------------------------------------------------
    def _pause(self, path):
        from pytorch_distributed_tpu.runtime import faults

        faults.check("pipeline.stage_stall", path)
        act = faults.hang_action("pipeline.stage_stall", path)
        if act is not None and act[0] == "stall":
            time.sleep(act[1])
        if self.delay_s > 0.0:
            time.sleep(self.delay_s)

    def _recv(self, src, tag):
        got = self.group.recv(self._act_buf, src, tag=tag)
        return np.array(got)  # the proto buffer is reused between recvs

    @staticmethod
    def _split(batch, num_microbatches: int) -> List[dict]:
        out = []
        for i in range(num_microbatches):
            mb = {}
            for k, v in batch.items():
                n = v.shape[0]
                if n % num_microbatches:
                    raise ValueError(
                        f"batch dim {n} not divisible by "
                        f"{num_microbatches} microbatches"
                    )
                size = n // num_microbatches
                mb[k] = np.asarray(v[i * size:(i + 1) * size])
            out.append(mb)
        return out

    @staticmethod
    def _fold(acc, tree):
        """Left fold in numpy — IEEE f32 adds in the same fixed order as
        ``lax.scan``'s accumulation, so the sum is the scan association."""
        leaves = _tree_leaves(tree)
        if acc is None:
            # own the accumulator: views of jax buffers are read-only
            return [np.array(x) for x in leaves]
        for a, b in zip(acc, leaves):
            np.add(a, np.asarray(b), out=a)
        return acc

    def step(self, params, opt_state, batch, buffers=None):
        """One optimizer step: returns ``(params, opt_state, metrics)``.
        ``buffers`` carries non-optimized replicas (the tied head wte on
        the last stage); updated in place via ``exchange_params``."""
        from pytorch_distributed_tpu.runtime import tracing

        mbs = self._split(batch, self.num_microbatches)
        stash: Dict[int, object] = {}
        dys: Dict[int, object] = {}
        dxs: Dict[int, np.ndarray] = {}
        grads_acc = None
        aux_acc = None
        grads_struct = None
        losses: List[float] = []
        st = self.stage
        for op, path in zip(self.program, self._paths):
            mb = op.mb
            if op.kind == RECV_ACT:
                stash[mb] = self._recv(st - 1, tag=f"act.m{mb}.s{st}")
            elif op.kind == SEND_ACT:
                self.group.send(
                    stash.pop((SEND_ACT, mb)), st + 1,
                    tag=f"act.m{mb}.s{st + 1}",
                )
            elif op.kind == RECV_GRAD:
                dys[mb] = self._recv(st + 1, tag=f"grad.m{mb}.s{st}")
            elif op.kind == SEND_GRAD:
                self.group.send(
                    dxs.pop(mb), st - 1, tag=f"grad.m{mb}.s{st - 1}"
                )
            elif op.kind == FWD:
                with tracing.span("pipeline.fwd", mb=mb, stage=st):
                    self._pause(path)
                    if self._last:
                        # forward runs inside the last stage's loss_grad
                        # program (value_and_grad); this slot only admits
                        # the microbatch into the pipe
                        if self._first:
                            stash[mb] = mbs[mb][self.ids_key]
                        continue
                    xin = (
                        mbs[mb][self.ids_key] if self._first
                        else stash.pop(mb)
                    )
                    stash[mb] = xin  # retired by the matching BWD
                    y = self._jits["fwd"](params, xin)
                    y.block_until_ready()
                    stash[(SEND_ACT, mb)] = np.asarray(y)
            else:  # BWD
                with tracing.span("pipeline.bwd", mb=mb, stage=st):
                    self._pause(path)
                    if self.num_stages == 1:
                        loss, grads = self._jits["loss_grad"](
                            params, stash.pop(mb)
                        )
                        _block_tree(grads)
                    elif self._last:
                        loss, grads, head_grad, dx = self._jits[
                            "loss_grad"
                        ](
                            params, buffers["head_wte"], stash.pop(mb),
                            mbs[mb][self.ids_key],
                        )
                        _block_tree(grads)
                        aux_acc = self._fold(aux_acc, head_grad)
                        dxs[mb] = np.asarray(dx)
                    elif self._first:
                        grads = self._jits["bwd"](
                            params, stash.pop(mb), dys.pop(mb)
                        )
                        _block_tree(grads)
                    else:
                        grads, dx = self._jits["bwd"](
                            params, stash.pop(mb), dys.pop(mb)
                        )
                        _block_tree(grads)
                        dxs[mb] = np.asarray(dx)
                    if grads_struct is None:
                        grads_struct = _tree_structure(grads)
                    grads_acc = self._fold(grads_acc, grads)
                    if self._last:
                        losses.append(float(loss))
        assert not stash and not dys and not dxs, (
            f"stage {st} retired the schedule with live state: "
            f"{list(stash)} {list(dys)} {list(dxs)}"
        )
        grads = _tree_unflatten(grads_struct, grads_acc)
        if self.group is not None and hasattr(
            self.programs, "exchange_grads"
        ):
            grads = self.programs.exchange_grads(
                self.group, self.stage, self.num_stages, grads,
                aux_acc[0] if aux_acc else None,
            )
        params, opt_state = self._jits["apply"](params, opt_state, grads)
        _block_tree(params)
        if self.group is not None and hasattr(
            self.programs, "exchange_params"
        ):
            self.programs.exchange_params(
                self.group, self.stage, self.num_stages, params, buffers
            )
        metrics = {}
        if losses:
            metrics["loss"] = float(np.mean(losses))
        return params, opt_state, metrics


def _tree_leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


def _tree_structure(tree):
    import jax

    return jax.tree_util.tree_structure(tree)


def _tree_unflatten(struct, leaves):
    import jax

    return jax.tree_util.tree_unflatten(struct, leaves)


def _block_tree(tree) -> None:
    for leaf in _tree_leaves(tree):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
