"""Train-step builder and epoch-loop Trainer.

Replaces the reference recipes' hot loop (forward / backward / allreduce /
optimizer.step with optional AMP scaling and grad accumulation,
BASELINE.json:5,9,10) with one jit-compiled function:

* gradient accumulation is a ``lax.scan`` over microbatches *inside* the
  step (the reference's ``no_sync()`` dance is unnecessary — there is no
  per-microbatch allreduce to suppress; the grad average is one collective
  emitted after the scan),
* BatchNorm stats thread through the scan carry,
* fp16 dynamic loss scaling (when a ``GradScaler`` is given) scales inside
  the grad computation and conditionally skips the optimizer update,
* the whole step is compiled by the Strategy with state shardings pinned.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_tpu.runtime import distributed as dist
from pytorch_distributed_tpu.runtime import tracing
from pytorch_distributed_tpu.runtime.compat import jit_cache_size
from pytorch_distributed_tpu.runtime.device import host_scalar
from pytorch_distributed_tpu.runtime.precision import GradScaler
from pytorch_distributed_tpu.runtime.prng import key_for
from pytorch_distributed_tpu.train.train_state import TrainState
from pytorch_distributed_tpu.train.metrics import (
    MeterState,
    MetricsWriter,
    ScalarMeter,
    TeeWriter,
)
from pytorch_distributed_tpu.utils.logging import get_logger

# loss_fn(params, batch_stats, batch, rng) ->
#     (loss, {"metrics": {...}, "batch_stats": new_stats_or_None})
LossFn = Callable[[Any, Any, Any, jax.Array], Tuple[jax.Array, Dict[str, Any]]]

logger = get_logger(__name__)

_EPOCH_END = object()  # loader-exhausted sentinel for the spanned fetch


def _accepts_rng(transform) -> bool:
    """Does ``transform`` take a second positional (rng) argument?

    Deliberately conservative: a pre-existing 1-arg transform must keep
    being called as ``transform(batch)``. The rng is passed only when
    the transform says so explicitly (``_ptd_takes_rng`` attribute, set
    by ``make_device_normalizer(flip=True)``) or its second positional
    parameter is REQUIRED (no default — such a callable could never have
    worked under the old 1-arg contract, so this can't change behavior
    for existing code). Defaulted second params (``lambda b, eps=1e-6``)
    and ``*args`` wrappers stay on the 1-arg call.
    """
    marked = getattr(transform, "_ptd_takes_rng", None)
    if marked is not None:
        return bool(marked)
    import inspect

    try:
        sig = inspect.signature(transform)
    except (TypeError, ValueError):  # builtins/callables without a sig
        return False
    required_positional = 0
    for p in sig.parameters.values():
        if p.kind in (
            inspect.Parameter.POSITIONAL_ONLY,
            inspect.Parameter.POSITIONAL_OR_KEYWORD,
        ) and p.default is inspect.Parameter.empty:
            required_positional += 1
    return required_positional >= 2


def _split_microbatches(batch, accum_steps: int):
    """[B, ...] -> [accum, B/accum, ...] on every leaf."""

    def split(x):
        if x.shape[0] % accum_steps != 0:
            raise ValueError(
                f"batch dim {x.shape[0]} not divisible by accum_steps={accum_steps}"
            )
        return x.reshape((accum_steps, x.shape[0] // accum_steps) + x.shape[1:])

    return jax.tree_util.tree_map(split, batch)


def _apply_update(state, grads, new_stats, loss_value, *, scaler,
                  scaling, ema_decay):
    """Post-sync optimizer / scaler-skip / EMA section — ONE definition
    shared by the scanned step and HostLoopStep, so the two paths'
    update math cannot drift (the cross-mode bit-identity pins depend
    on these being the same expressions). Returns
    ``(new_state, extra_metrics)``."""
    extra = {}
    if scaling:
        new_scaler_state, grads_ok = scaler.functional_update(
            grads, state.scaler_state
        )
        candidate = state.apply_gradients(
            grads, batch_stats=new_stats, scaler_state=new_scaler_state,
            loss_value=loss_value,
        )
        skipped = state.replace(
            scaler_state=new_scaler_state, step=state.step + 1
        )
        new_state = jax.tree_util.tree_map(
            lambda a, b: jnp.where(grads_ok, a, b), candidate, skipped
        )
        extra["loss_scale"] = new_scaler_state.scale
        extra["grads_finite"] = grads_ok.astype(jnp.float32)
    else:
        new_state = state.apply_gradients(
            grads, batch_stats=new_stats, loss_value=loss_value
        )

    if ema_decay is not None:
        if state.ema_params is None:
            raise ValueError(
                "ema_decay set but the state has no shadow params — "
                "create it with TrainState.create(..., ema=True)"
            )
        d = ema_decay
        new_state = new_state.replace(
            ema_params=jax.tree_util.tree_map(
                # accumulate in the shadow's dtype (f32): see
                # TrainState.create's half-ulp note
                lambda e, p: d * e + (1.0 - d) * p.astype(e.dtype),
                new_state.ema_params, new_state.params,
            )
        )
    return new_state, extra


def build_train_step(
    loss_fn: LossFn,
    *,
    accum_steps: int = 1,
    scaler: Optional[GradScaler] = None,
    batch_transform: Optional[Callable[[Any], Any]] = None,
    grad_compression: Optional[str] = None,
    ema_decay: Optional[float] = None,
    overlap_accum: bool = False,
    reduce_schedule: str = "step",
) -> Callable[[TrainState, Any], Tuple[TrainState, Dict[str, jax.Array]]]:
    """Build ``step(state, batch) -> (state, metrics)`` for jit/Strategy.compile.

    ``accum_steps > 1`` splits the (global) batch into microbatches scanned
    sequentially — the ZeRO-1/GPT-2 recipe shape (BASELINE.json:10) — giving
    the memory profile of small batches with the optimizer math of the full
    batch.

    ``batch_transform`` runs ON-DEVICE inside the jitted step, before
    microbatch splitting — e.g. ``ImageBatchPipeline.device_normalizer()``
    so uint8 batches ship over the host link and normalize on-chip (the
    default ingest path). A transform that takes TWO positional args is
    called as ``transform(batch, rng)`` with a PRNG key folded from the
    step's stream — the hook for fused on-device augmentation (e.g.
    ``make_device_normalizer(..., flip=True)``); replayed augmentations
    on resume come free because the key derives from ``state.step``.

    ``grad_compression`` ("bf16"/"fp16"/"int8") compresses the
    multi-process gradient sync on the wire (see
    ``parallel.ddp.sync_grads``); it has no effect in single-controller
    SPMD mode, where grad reduction is a compiler-inserted collective.

    ``ema_decay`` maintains shadow parameters (the ModelEMA idiom:
    ``ema = d*ema + (1-d)*params`` after every optimizer update) — create
    the state with ``TrainState.create(..., ema=True)``; evaluate the
    shadow via ``TrainerConfig(eval_with_ema=True)``.

    ``overlap_accum=True`` (opt-in, the multi-process/1-device-per-rank
    path) hoists the microbatch loop OUT of ``lax.scan`` into
    host-dispatched programs so gradient sync can pipeline with the
    step's own work: per-microbatch grads are fetched as JAX's async
    dispatch computes the next microbatch, accumulated straight into
    the grad-sync engine's wire staging in fixed microbatch order (the
    exact left-fold ``lax.scan`` uses — bit-identical local sums), and
    the bucketed ring reduce drains on a comm thread while the host
    finishes accumulating later buckets / staging the next batch (the
    ``begin()``/``finish()`` split exposes the overlap window to custom
    loops). The returned step is a :class:`HostLoopStep` — a callable
    with the same ``(state, batch) -> (state, metrics)`` contract that
    the Trainer uses as-is (it compiles its own three programs: prep,
    per-microbatch grad, apply — each exactly once). See DESIGN.md §19
    for the bit-exactness argument and the honest 1-core limits.
    """
    if overlap_accum:
        return HostLoopStep(
            loss_fn, accum_steps=accum_steps, scaler=scaler,
            batch_transform=batch_transform,
            grad_compression=grad_compression, ema_decay=ema_decay,
            reduce_schedule=reduce_schedule,
        )
    if reduce_schedule != "step":
        raise ValueError(
            "reduce_schedule is an overlap_accum option — the scanned "
            "step has exactly one (end-of-step) reduce"
        )
    if ema_decay is not None and not 0.0 <= ema_decay < 1.0:
        # d=1 freezes the shadow at init (eval_with_ema then silently
        # scores random weights); d>1 diverges
        raise ValueError(f"ema_decay must be in [0, 1), got {ema_decay}")
    scaling = scaler is not None and scaler.enabled
    transform_takes_rng = (
        batch_transform is not None and _accepts_rng(batch_transform)
    )

    def grad_fn(params, batch_stats, mb, rng, scaler_state):
        def scaled_loss(p):
            loss, aux = loss_fn(p, batch_stats, mb, rng)
            if scaling:
                loss = scaler.scale_value(loss, scaler_state)
            return loss, aux

        (_, aux), grads = jax.value_and_grad(scaled_loss, has_aux=True)(params)
        if scaling:
            grads = scaler.unscale_grads(grads, scaler_state)
        return grads, aux

    def step(state: TrainState, batch):
        rng = key_for(state.step)
        if batch_transform is not None:
            if transform_takes_rng:
                # a key decorrelated from the loss/dropout stream, still
                # derived from state.step (resume replays augmentation)
                batch = batch_transform(
                    batch, jax.random.fold_in(rng, 0x617567)  # "aug"
                )
            else:
                batch = batch_transform(batch)

        if accum_steps == 1:
            grads, aux = grad_fn(
                state.params, state.batch_stats, batch, rng, state.scaler_state
            )
            metrics = dict(aux.get("metrics", {}))
            new_stats = aux.get("batch_stats", state.batch_stats)
        else:
            mbs = _split_microbatches(batch, accum_steps)
            zero_grads = jax.tree_util.tree_map(jnp.zeros_like, state.params)

            def body(carry, mb):
                grads_acc, stats, metrics_acc = carry
                k = jax.random.fold_in(rng, metrics_acc["_i"].astype(jnp.int32))
                grads, aux = grad_fn(state.params, stats, mb, k, state.scaler_state)
                grads_acc = jax.tree_util.tree_map(jnp.add, grads_acc, grads)
                stats = aux.get("batch_stats", stats)
                m = dict(aux.get("metrics", {}))
                m["_i"] = metrics_acc["_i"] + 1
                for key in m:
                    if key != "_i" and key in metrics_acc:
                        m[key] = metrics_acc[key] + m[key]
                return (grads_acc, stats, m), None

            # seed metric accumulators with zeros from a traced first call
            probe_metrics = {"_i": jnp.zeros((), jnp.float32)}
            first_mb = jax.tree_util.tree_map(lambda x: x[0], mbs)
            _, probe_aux = jax.eval_shape(
                lambda: grad_fn(
                    state.params, state.batch_stats, first_mb, rng,
                    state.scaler_state,
                )
            )
            for key, v in probe_aux.get("metrics", {}).items():
                probe_metrics[key] = jnp.zeros(v.shape, v.dtype)

            (grads_sum, new_stats, metrics_sum), _ = jax.lax.scan(
                body, (zero_grads, state.batch_stats, probe_metrics), mbs
            )
            inv = 1.0 / accum_steps
            grads = jax.tree_util.tree_map(lambda g: g * inv, grads_sum)
            metrics = {
                k: v * inv for k, v in metrics_sum.items() if k != "_i"
            }

        # True multi-process mode (hostring backend): per-rank grads must be
        # averaged across ranks, DDP-style. Single-controller SPMD skips
        # this — sharding propagation already psums replicated-param grads.
        from pytorch_distributed_tpu.parallel import ddp

        if ddp.is_multiprocess():
            grads = ddp.sync_grads(grads, compress=grad_compression)

        # metric-driven optimizers (optim.ReduceLROnPlateau) read the loss
        # through the extra-args channel; None when the loss_fn reports no
        # "loss" metric
        loss_value = metrics.get("loss")
        new_state, extra = _apply_update(
            state, grads, new_stats, loss_value,
            scaler=scaler, scaling=scaling, ema_decay=ema_decay,
        )
        metrics.update(extra)
        return new_state, metrics

    # introspection for Trainer guards: distinguishes "built by this
    # factory without EMA" (attr None) from a user's custom step (absent)
    step._ptd_ema_decay = ema_decay
    return step


class HostLoopStep:
    """``build_train_step(overlap_accum=True)``'s step: the microbatch
    loop runs on the HOST so gradient sync can pipeline.

    Same ``(state, batch) -> (state, metrics)`` contract as the jitted
    step, compiled as exactly THREE programs (each once): ``prep``
    (batch transform + microbatch split), ``grad`` (one microbatch's
    gradients + metrics + batch_stats, called ``accum_steps`` times per
    step with the microbatch index as a traced argument), and ``apply``
    (the identical post-sync optimizer/scaler/EMA section). Between
    them the host fetches each microbatch's grads while JAX's async
    dispatch executes the next one, folds them into the grad-sync
    engine's wire staging in fixed microbatch order — the same
    left-fold association ``lax.scan`` uses, so the local sums are
    bit-identical to the scanned path's — and the bucketed ring reduce
    drains on the comm thread.

    ``begin(state, batch) -> pending`` / ``finish(pending)`` split the
    step at the point where every bucket is enqueued: a custom loop
    stages its NEXT batch between the two calls and that work runs
    while the ring drains (the bench's ``overlap`` phase and the
    DataLoader's producer thread both live in that window).
    ``__call__`` is ``finish(begin(...))`` — what the Trainer uses.

    Scope (documented, not discovered): the multi-process hostring /
    single-device-per-rank path. SPMD strategies keep the scanned step
    — a host loop cannot carry their shardings. ``grad_compression``
    supports ``None`` and ``"int8"`` (with error feedback); the half
    casts stay on the scanned path.
    """

    _ptd_host_step = True

    def __init__(self, loss_fn, *, accum_steps=1, scaler=None,
                 batch_transform=None, grad_compression=None,
                 ema_decay=None, reduce_schedule="step"):
        if ema_decay is not None and not 0.0 <= ema_decay < 1.0:
            raise ValueError(
                f"ema_decay must be in [0, 1), got {ema_decay}"
            )
        if grad_compression not in (None, "int8"):
            raise ValueError(
                "overlap_accum supports grad_compression None or "
                f"'int8', got {grad_compression!r} — half-precision "
                "wire casts stay on the scanned path"
            )
        if reduce_schedule not in ("step", "microbatch"):
            raise ValueError(
                f"reduce_schedule must be 'step' or 'microbatch', "
                f"got {reduce_schedule!r}"
            )
        if reduce_schedule == "microbatch" and grad_compression == "int8":
            # per-item error-feedback residuals assume one quantized
            # sync per step; A syncs/step would fold A residual updates
            # into one leaf — refuse rather than silently change the math
            raise ValueError(
                "reduce_schedule='microbatch' does not compose with "
                "grad_compression='int8' (error feedback is per step)"
            )
        if accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        self.reduce_schedule = reduce_schedule
        self.accum_steps = accum_steps
        self.scaler = scaler
        self.ema_decay = ema_decay
        self.grad_compression = grad_compression
        self._ptd_ema_decay = ema_decay
        self.last_sync_stats: Optional[Dict[str, float]] = None
        scaling = scaler is not None and scaler.enabled
        self._scaling = scaling
        takes_rng = (
            batch_transform is not None and _accepts_rng(batch_transform)
        )

        def grad_fn(params, batch_stats, mb, rng, scaler_state):
            def scaled_loss(p):
                loss, aux = loss_fn(p, batch_stats, mb, rng)
                if scaling:
                    loss = scaler.scale_value(loss, scaler_state)
                return loss, aux

            (_, aux), grads = jax.value_and_grad(
                scaled_loss, has_aux=True
            )(params)
            if scaling:
                grads = scaler.unscale_grads(grads, scaler_state)
            return grads, aux

        def prep(state, batch, accum):
            # ``accum`` is static: the default path always passes
            # accum_steps (one compile); a microbatch plan passes its
            # local count — one extra compile per distinct count, which
            # the rebalance cadence bounds
            rng = key_for(state.step)
            if batch_transform is not None:
                if takes_rng:
                    batch = batch_transform(
                        batch, jax.random.fold_in(rng, 0x617567)
                    )
                else:
                    batch = batch_transform(batch)
            return _split_microbatches(batch, accum)

        def grad_one(state, batch_stats, mb, i):
            rng = key_for(state.step)
            # accum==1 keeps the scanned/plain path's key exactly;
            # accum>1 folds the microbatch index like the scan body
            k = rng if accum_steps == 1 else jax.random.fold_in(
                rng, i.astype(jnp.int32)
            )
            grads, aux = grad_fn(
                state.params, batch_stats, mb, k, state.scaler_state
            )
            return (
                grads,
                dict(aux.get("metrics", {})),
                aux.get("batch_stats", batch_stats),
            )

        def apply(state, grads, new_stats, loss_value):
            # the SAME shared section the scanned step jits — any drift
            # here would break the cross-mode bit-identity pins
            return _apply_update(
                state, grads, new_stats, loss_value,
                scaler=scaler, scaling=scaling, ema_decay=ema_decay,
            )

        self._prep = jax.jit(prep, static_argnums=(2,))
        self._grad = jax.jit(grad_one)
        self._apply_fn = apply
        self._apply = None  # built lazily: loss presence is static
        self._apply_has_loss = None
        self._mb_plan: Optional[Tuple[int, int, int]] = None

    # -- heterogeneity-aware microbatch counts (r15) ------------------------
    def set_microbatch_plan(self, local_steps: int, total_steps: int,
                            offset: int = 0) -> None:
        """Run ``local_steps`` microbatches on THIS rank while the world
        runs ``total_steps`` in aggregate — the HostLoopStep half of the
        r15 heterogeneity balancer (``train/balance.microbatch_counts``
        derives the per-rank counts from the same rate telemetry the
        elastic engine allgathers).

        Contract: per-MICROBATCH size stays what ``accum_steps`` implied
        — the balancer moves microbatch COUNT between ranks, never size
        — so the caller feeds this rank ``local_steps x microbatch``
        samples per step, and the ring exchange scales local sums by
        ``world / total_steps`` so the averaged update is the mean over
        all ``total_steps`` microbatches, exactly the quantity the even
        split computes. Unlike the elastic engine's fixed-shard fold
        this is NOT bit-identical to the even split (per-rank partial
        sums regroup the summation), but it is deterministic and
        lockstep: the collective sequence per step (one bucketed sync)
        is independent of the per-rank count.

        ``offset`` is this rank's first GLOBAL microbatch index (the
        contiguous-runs layout ``balance.assignment_from_counts`` uses:
        rank r starts after the lower ranks' counts). Each grad call is
        rng-keyed by its global index, so microbatch j draws the same
        key whichever rank computes it — a solo run over the same
        ``total_steps`` microbatches is the reference an uneven world
        converges to (last-ulp: summation association differs).

        Changing ``local_steps`` changes ``prep``'s input batch shape —
        one additional compile of the prep/grad programs per DISTINCT
        local count (bounded by the number of rebalances), which the
        recompile sentinel treats as a new warm-up baseline.

        Refused for ``reduce_schedule="microbatch"`` (its collective
        count per step IS the local count — uneven counts desync the
        ring) and for ``grad_compression="int8"`` (the error-feedback
        parity claims are pinned on the even path). Call with
        ``local == total == accum_steps`` to restore the default
        behavior (clears the plan). Any other stored ``local == total``
        plan is a SOLO contract — on a multi-rank ring it would mean
        every rank duplicates every microbatch (and the even ``1/total``
        scale would silently become ``world/total``), so ``begin()``
        refuses the combination loudly.
        """
        local, total = int(local_steps), int(total_steps)
        off = int(offset)
        if local < 1 or total < local:
            raise ValueError(
                f"need 1 <= local <= total, got local={local} "
                f"total={total}"
            )
        if off < 0 or off + local > total:
            raise ValueError(
                f"offset {off} + local {local} must fit in total {total}"
            )
        if self.accum_steps == 1 and total != local:
            raise ValueError(
                "an uneven microbatch plan needs accum_steps > 1 at "
                "build time (accum_steps==1 steps key their single "
                "microbatch off the raw step rng — there is no global "
                "index to rebalance over)"
            )
        if self.reduce_schedule == "microbatch" and local != total:
            raise ValueError(
                "set_microbatch_plan does not compose with "
                "reduce_schedule='microbatch': per-rank counts ARE the "
                "per-step collective counts there — uneven counts would "
                "desync the ring"
            )
        if self.grad_compression == "int8" and local != total:
            raise ValueError(
                "set_microbatch_plan does not compose with "
                "grad_compression='int8' (q8 error-feedback parity is "
                "pinned on the even split)"
            )
        if local == total == self.accum_steps:
            # the documented restore: identical to never having set a
            # plan, so clear it — begin() takes the default path (and a
            # multi-rank ring keeps its exact 1/A scale)
            self._mb_plan = None
            return
        self._mb_plan = (local, total, off)

    # -- introspection ------------------------------------------------------
    def compile_counts(self) -> Dict[str, Optional[int]]:
        from pytorch_distributed_tpu.runtime.compat import jit_cache_size

        return {
            "prep": jit_cache_size(self._prep),
            "grad": jit_cache_size(self._grad),
            "apply": (
                jit_cache_size(self._apply)
                if self._apply is not None else 0
            ),
        }

    # -- the two-phase step -------------------------------------------------
    def begin(self, state, batch):
        """Dispatch + fetch + accumulate; returns with every grad-sync
        bucket ENQUEUED — work done by the caller before ``finish`` runs
        concurrently with the ring drain.

        ``reduce_schedule="step"`` (default): microbatch grads fold into
        the wire staging as local sums (bit-identical to the scanned
        step's left fold) and ONE bucketed reduce drains at the end —
        the lowest-wire-volume schedule, the right one when comm rides
        a memcpy-bound transport. ``reduce_schedule="microbatch"``: each
        microbatch's grads ring-reduce as soon as they land, while
        JAX's async dispatch executes the NEXT microbatch — true
        structural comm/compute overlap (the veScale shape), at
        ``accum_steps`` x the wire volume; reduced sums fold on the
        host in fixed microbatch order (the elastic_world fixed-shard
        discipline), so the result is deterministic and lockstep across
        ranks, and equals the step schedule's up to summation
        association (last-ulp — see DESIGN.md §19).
        """
        from pytorch_distributed_tpu.parallel.overlap import get_engine
        from pytorch_distributed_tpu.runtime import distributed as dist

        plan = self._mb_plan
        A = self.accum_steps if plan is None else plan[0]
        offset = 0 if plan is None else plan[2]
        mbs = self._prep(state, batch, A)
        stats = state.batch_stats
        outs = []
        for i in range(A):
            mb = jax.tree_util.tree_map(lambda x, _i=i: x[_i], mbs)
            # a microbatch plan keys each grad by its GLOBAL microbatch
            # index (this rank covers [offset, offset+local)), so the
            # same microbatch draws the same rng whichever rank computes
            # it — the elastic engine's ownership-free key discipline
            grads, m, stats = self._grad(
                state, stats, mb, np.int32(offset + i)
            )
            outs.append((grads, m))
        inv = 1.0 / A
        ring = dist.multiprocess_ring()
        use_ring = ring is not None and ring.world_size > 1
        if plan is not None:
            total = plan[1]
            if use_ring:
                if A >= total:
                    raise RuntimeError(
                        f"microbatch plan local={A} == total={total} on "
                        f"a {ring.world_size}-rank ring: every rank "
                        "would duplicate every microbatch and the "
                        "reduced gradient would be scaled by world — "
                        "pass local == total == accum_steps to clear "
                        "the plan, or a per-rank share summing to total"
                    )
                # ring "avg" divides the summed contributions by world,
                # so scaling local sums by world/total makes the reduced
                # result the mean over ALL total microbatches — the even
                # split's world/(A*world) == 1/A exactly, uneven worlds
                # the aggregate-speed generalization of it
                wire_scale = ring.world_size / total
            elif total != A:
                raise RuntimeError(
                    f"microbatch plan local={A} < total={total} needs a "
                    "multiprocess ring to cover the remaining "
                    "microbatches — solo runs must set local == total"
                )
            else:
                wire_scale = inv
        else:
            wire_scale = inv
        per_mb = use_ring and self.reduce_schedule == "microbatch"
        treedef = None
        session = None
        local_acc = None
        mb_acc = None
        mb_comm = mb_exposed = 0.0
        m_acc: Dict[str, Any] = {}
        for i, (grads, m) in enumerate(outs):
            leaves, treedef = jax.tree_util.tree_flatten(grads)
            np_leaves = [np.asarray(x) for x in leaves]
            for k, v in m.items():
                v = np.asarray(v)
                m_acc[k] = v if k not in m_acc else m_acc[k] + v
            if per_mb:
                # enqueue mb i FIRST, then drain mb i-1: i-1's ring ran
                # under mb i's in-flight compute AND under this fold +
                # enqueue, so only its residual tail is exposed. The
                # staggered generations make this safe: i-1's staging is
                # folded (copied) here, before generation reuse at i+1.
                prev = session
                session = get_engine(ring).begin_accum(
                    [(x.shape, x.dtype) for x in np_leaves],
                    quantize=False,
                )
                session.finish(np_leaves, scale=1.0)
                if prev is not None:
                    done, st = prev.drain()
                    mb_comm += st["comm_s"]
                    mb_exposed += st["exposed_s"]
                    mb_acc = self._fold_reduced(mb_acc, done)
            elif use_ring:
                if session is None:
                    session = get_engine(ring).begin_accum(
                        [(x.shape, x.dtype) for x in np_leaves],
                        quantize=self.grad_compression == "int8",
                    )
                if i < A - 1:
                    session.add(np_leaves)
                else:
                    # bucket-staggered: each bucket's ring reduce starts
                    # while the host accumulates/scales the next bucket
                    session.finish(np_leaves, scale=wire_scale)
            else:
                if local_acc is None:
                    local_acc = [
                        np.array(x, copy=True) for x in np_leaves
                    ]
                else:
                    for dst, src in zip(local_acc, np_leaves):
                        np.add(dst, src, out=dst)
        metrics = {
            k: (v * np.float32(inv) if A > 1 else v)
            for k, v in m_acc.items()
        }
        return {
            "state": state,
            "session": session,
            "per_mb": per_mb,
            "mb_acc": mb_acc,
            "mb_comm": mb_comm,
            "mb_exposed": mb_exposed,
            "local_acc": local_acc,
            "treedef": treedef,
            "stats": stats,
            "metrics": metrics,
            "inv": inv,
        }

    @staticmethod
    def _fold_reduced(acc, leaves):
        if acc is None:
            return [np.array(x, copy=True) for x in leaves]
        for dst, src in zip(acc, leaves):
            np.add(dst, src, out=dst)
        return acc

    def finish(self, pending):
        """Drain the ring, apply the update, return (state, metrics)."""
        state = pending["state"]
        metrics = pending["metrics"]
        inv = np.float32(pending["inv"])
        if pending["per_mb"]:
            done, st = pending["session"].drain()
            comm = pending["mb_comm"] + st["comm_s"]
            exposed = pending["mb_exposed"] + st["exposed_s"]
            leaves = self._fold_reduced(pending["mb_acc"], done)
            if inv != 1.0:  # the pending's OWN count (a microbatch
                # plan may differ from the built accum_steps)
                for leaf in leaves:
                    np.multiply(leaf, inv.astype(leaf.dtype), out=leaf)
            self.last_sync_stats = {
                "comm_s": comm,
                "exposed_s": exposed,
                "hidden_s": max(comm - exposed, 0.0),
            }
        elif pending["session"] is not None:
            leaves, sync_stats = pending["session"].drain()
            self.last_sync_stats = sync_stats
        else:
            leaves = pending["local_acc"]
            if inv != 1.0:  # ditto: the pending's own count
                for leaf in leaves:
                    np.multiply(
                        leaf, inv.astype(leaf.dtype), out=leaf
                    )
            self.last_sync_stats = None
        grads = jax.tree_util.tree_unflatten(pending["treedef"], leaves)
        loss_value = metrics.get("loss")
        if self._apply is None:
            self._apply_has_loss = loss_value is not None
            fn = self._apply_fn
            if self._apply_has_loss:
                self._apply = jax.jit(fn, donate_argnums=(0,))
            else:
                self._apply = jax.jit(
                    lambda s, g, st: fn(s, g, st, None),
                    donate_argnums=(0,),
                )
        if self._apply_has_loss != (loss_value is not None):
            raise ValueError(
                "loss metric presence changed between steps — the apply "
                "program's signature is static"
            )
        args = (state, grads, pending["stats"])
        if self._apply_has_loss:
            args = args + (np.float32(loss_value),)
        new_state, extra = self._apply(*args)
        metrics.update(extra)
        return new_state, metrics

    def __call__(self, state, batch):
        return self.finish(self.begin(state, batch))


@dataclasses.dataclass
class TrainerConfig:
    epochs: int = 1
    log_every: int = 50
    ckpt_dir: Optional[str] = None
    ckpt_every_steps: Optional[int] = None  # None -> end of epoch only
    eval_every_epochs: int = 1
    eval_with_ema: bool = False  # evaluate shadow (EMA) params instead
    samples_axis: str = "image"  # batch leaf whose dim0 counts samples
    donate_batch: Optional[bool] = None  # donate batch buffers into the
    # train step (each loader batch is consumed exactly once, so the
    # uint8 ingest buffer frees as soon as the fused normalize reads
    # it). None = auto: on for accelerators, off on the CPU backend
    # (XLA:CPU rarely aliases them and warns per executable instead)
    async_checkpoint: bool = False  # overlap ckpt IO with training
    metrics_path: Optional[str] = None  # JSONL scalar log (rank 0)
    tensorboard_dir: Optional[str] = None  # TB event files (rank 0)
    max_steps_per_epoch: Optional[int] = None  # bound endless streams
    # failure detection / elastic recovery (train/elastic.py):
    handle_preemption: bool = True  # SIGTERM -> checkpoint -> Preempted
    stall_timeout_s: Optional[float] = None  # watchdog hang detection
    log_mfu: bool = False  # append achieved TFLOP/s + MFU to step logs
    # (costs one AOT lower+compile of the train step on the first batch —
    # a disk hit when the persistent compilation cache is enabled)
    keep_checkpoints: Optional[int] = None  # with ckpt_every_steps: save
    # step-<N> tags and retain only the newest N (latest/best untouched)
    keep_best: Optional[str] = None  # eval metric name: save tag 'best'
    # whenever it improves
    best_mode: str = "max"  # 'max' (accuracy-like) or 'min' (loss-like)
    halt_on_nonfinite: int = 3  # consecutive non-finite LOGGED losses
    # before raising TrainingDiverged (0 disables). NaN weights never
    # recover, so persistent NaN means every later step is wasted chip
    # time; the threshold tolerates fp16's transient overflow-and-skip
    # window (GradScaler keeps params finite while the scale decays).
    early_stop_patience: Optional[int] = None  # evals without improvement
    # in the keep_best metric (same best_mode) before fit() stops early —
    # the HF EarlyStoppingCallback idiom; requires keep_best + eval_step
    eval_finalize: Optional[Callable] = None  # means -> means transform
    # after eval aggregation (derive ratio metrics like F1/MCC from
    # aggregated confusion rates — train.f1_finalize); keep_best and
    # early stopping see the finalized names
    trace_dir: Optional[str] = None  # with trace_steps: profiler output
    trace_steps: Optional[tuple] = None  # (start, stop) host steps to
    # trace — the torch.profiler schedule(wait/active) idiom: capture a
    # small mid-training window (past compiles and warmup) instead of
    # wrapping the whole run in maybe_trace
    trace: Optional[str] = None  # span-tracer output dir (runtime/
    # tracing.py): Trainer construction arms the process-wide recorder
    # (so the pre-fit restore_checkpoint() lands too), every
    # instrumented site (trainer step loop, ingest producer threads,
    # a serve engine sharing the process) lands on one timeline, and
    # fit() teardown writes <trace>/trace.json (Perfetto-loadable) plus
    # per-span rollups into the metrics stream. Distinct from
    # trace_dir/trace_steps, which drive the XLA device profiler —
    # this one is the always-cheap host-side span timeline.


class TrainingDiverged(RuntimeError):
    """Raised when the logged training loss stays non-finite — the run is
    producing garbage and burning accelerator time; restart from the last
    finite checkpoint with a lower LR / different seed."""


class Trainer:
    """Epoch loop: feed, step, meter, log, checkpoint, eval.

    The reference spreads this boilerplate across each recipe script; here
    recipes assemble a Trainer from (state, strategy, step, loaders) and
    keep only model/loss definitions local.
    """

    def __init__(
        self,
        state: TrainState,
        strategy,
        train_step,
        train_loader,
        *,
        eval_step=None,
        eval_loader=None,
        config: Optional[TrainerConfig] = None,
    ):
        self.config = config or TrainerConfig()
        self.strategy = strategy
        if (
            self.config.eval_with_ema
            and getattr(train_step, "_ptd_ema_decay", "custom") is None
        ):  # ema=True state + a builder step that never updates the
            # shadow would silently evaluate frozen init weights
            raise ValueError(
                "eval_with_ema=True but the train step was built without "
                "ema_decay — pass build_train_step(..., ema_decay=...)"
            )
        self.state = strategy.place(state)
        # a new Trainer is a new training run: q8 error-feedback
        # residuals from a previous run in this process (same leaf
        # shapes, same engine) would leak its LAST gradient's
        # quantization error into this run's first sync
        from pytorch_distributed_tpu.parallel.ddp import (
            reset_error_feedback,
        )

        reset_error_feedback()
        donate_batch = self.config.donate_batch
        if donate_batch is None:
            from pytorch_distributed_tpu.runtime.device import platform

            donate_batch = platform() != "cpu"
        if getattr(train_step, "_ptd_host_step", False):
            # build_train_step(overlap_accum=True): the step drives its
            # own host microbatch loop and compiles its own programs —
            # jitting it through the strategy would trace the loop away.
            # Scope: the hostring / 1-device-per-rank path only.
            if jax.device_count() > 1:
                raise ValueError(
                    "overlap_accum steps drive a host microbatch loop "
                    "and cannot carry multi-device SPMD shardings — "
                    "use the scanned build_train_step on this mesh"
                )
            self.train_step = train_step
        else:
            try:
                self.train_step = strategy.compile(
                    train_step, self.state, donate_batch=donate_batch
                )
            except TypeError:  # user strategy predating donate_batch
                self.train_step = strategy.compile(train_step, self.state)
        self.eval_step = (
            jax.jit(eval_step) if eval_step is not None else None
        )
        self.train_loader = train_loader
        self.eval_loader = eval_loader
        self.meter = ScalarMeter()
        self.metrics_writer = None
        if dist.multiprocess_ring() is None or dist.get_rank() == 0:
            writers = []
            if self.config.metrics_path:
                writers.append(MetricsWriter(self.config.metrics_path))
            if self.config.tensorboard_dir:
                from pytorch_distributed_tpu.utils.tensorboard import (
                    TensorBoardWriter,
                )

                writers.append(TensorBoardWriter(self.config.tensorboard_dir))
            if len(writers) == 1:
                self.metrics_writer = writers[0]
            elif writers:
                self.metrics_writer = TeeWriter(writers)
        self.last_eval_metrics: Dict[str, float] = {}
        # Host-side mirror of state.step (monotonic Python int, +1 per
        # train_step call — apply_gradients increments exactly once per
        # call, including the scaler's skip path). Control flow (logging,
        # checkpoint cadence, preemption) reads this instead of
        # state.step: it needs no device sync, and it is safe to read
        # from watchdog/test threads while state's buffers are donated
        # into the in-flight compiled step.
        self.host_step = int(host_scalar(self.state.step))
        self._first_epoch = 0
        self._resume_skip_batches = 0
        # live data cursor (epoch + batches consumed this epoch): saved
        # next to every checkpoint so resume — and an elastic resize —
        # replays from the exact batch, not a steps-per-epoch heuristic
        self._cursor_epoch = 0
        self._cursor_offset = 0
        self._preemption = None
        self._watchdog = None
        self._async_ckpt = None
        # goodput clock starts at construction: setup/compile before the
        # first step is honestly "other", not productive time
        self._goodput = tracing.GoodputAccount()
        # arm the span tracer HERE, not in fit(): every recipe calls
        # restore_checkpoint() first, and its train.restore span must
        # land on the timeline (fit teardown exports and disarms)
        self._own_tracer = (
            tracing.configure(self.config.trace)
            if self.config.trace else None
        )
        self._step_flops = None  # per-step FLOPs (log_mfu), set lazily
        self._best_value: Optional[float] = None  # keep_best tracking
        # (resets on resume: a restored run re-establishes its best)
        self._nonfinite_logs = 0  # consecutive non-finite logged losses
        self._es_best: Optional[float] = None  # early-stop tracking
        self._es_stale = 0
        if self.config.best_mode not in ("max", "min"):
            raise ValueError(
                f"best_mode must be 'max' or 'min', "
                f"got {self.config.best_mode!r}"
            )
        if (
            self.config.keep_checkpoints is not None
            and self.config.keep_checkpoints < 1
        ):  # fail at construction, not at the first mid-training prune
            raise ValueError(
                f"keep_checkpoints must be >= 1, "
                f"got {self.config.keep_checkpoints}"
            )
        if (
            self.config.keep_checkpoints is not None
            and not self.config.ckpt_every_steps
        ):  # retention only acts on step-<N> tags, which only
            # ckpt_every_steps produces — otherwise it is silently inert
            raise ValueError(
                "keep_checkpoints requires ckpt_every_steps: retention "
                "prunes step-tagged checkpoints, which are only written "
                "on the ckpt_every_steps cadence"
            )
        if self.config.early_stop_patience is not None:
            if self.config.early_stop_patience < 1:
                raise ValueError(
                    f"early_stop_patience must be >= 1, "
                    f"got {self.config.early_stop_patience}"
                )
            if self.config.keep_best is None or eval_step is None:
                # the stop condition is "the keep_best eval metric
                # stopped improving" — without both it can never trigger
                raise ValueError(
                    "early_stop_patience requires keep_best (the watched "
                    "metric name) and an eval_step"
                )
        if (self.config.trace_steps is not None) != (
            self.config.trace_dir is not None
        ):
            raise ValueError(
                "trace_dir and trace_steps come together: the pair "
                "means 'profile host steps [start, stop) into this dir'"
            )
        if self.config.trace_steps is not None:
            a, b = self.config.trace_steps
            if not 0 <= a < b:
                raise ValueError(
                    f"trace_steps must be (start, stop) with "
                    f"0 <= start < stop, got {self.config.trace_steps}"
                )
        self._tracing = False
        if self.config.halt_on_nonfinite < 0:
            raise ValueError(
                f"halt_on_nonfinite must be >= 0 (0 disables), "
                f"got {self.config.halt_on_nonfinite}"
            )
        if self.config.async_checkpoint:
            from pytorch_distributed_tpu.train.checkpoint import (
                AsyncCheckpointer,
            )

            self._async_ckpt = AsyncCheckpointer()

    # -- checkpointing ------------------------------------------------------
    def save_checkpoint(self, tag: str = "latest") -> Optional[str]:
        if self.config.ckpt_dir is None:
            return None
        # hostring backend: state is fully replicated per rank, rank 0
        # writes alone. SPMD multi-host: every process must participate
        # (each writes its addressable shards; process 0 commits).
        if dist.multiprocess_ring() is not None and dist.get_rank() != 0:
            return None
        from pytorch_distributed_tpu.train.checkpoint import save_checkpoint

        with self._accounted("train.checkpoint", "checkpoint", tag=tag):
            if self._async_ckpt is not None:
                self._async_ckpt.save(
                    self.config.ckpt_dir, self.state, tag=tag
                )
                path = os.path.join(self.config.ckpt_dir, tag)
            else:
                path = save_checkpoint(
                    self.config.ckpt_dir, self.state, tag=tag
                )
        logger.info("checkpoint saved: %s (step %d)", path, self.host_step)
        if jax.process_index() == 0:  # the commit owner, like best/prune
            from pytorch_distributed_tpu.train.checkpoint import (
                save_sampler_cursor,
            )

            save_sampler_cursor(
                self.config.ckpt_dir, step=self.host_step,
                epoch=self._cursor_epoch, offset=self._cursor_offset,
            )
        if self._watchdog is not None:
            self._watchdog.tick()  # a slow (sharded) save is not a hang
        return path

    def _prune_checkpoints(self) -> None:
        """Prune-before-save: trims to keep-1 (the imminent save supplies
        the newest survivor) so async saves stay overlapped with training,
        but NEVER below one — deleting the last step checkpoint before its
        replacement lands would leave a hard-kill window with nothing to
        resume from. Steady state holds keep checkpoints (keep+1 briefly
        for keep=1)."""
        cfg = self.config
        if not (cfg.keep_checkpoints and cfg.ckpt_dir):
            return
        # only the commit owner prunes (matches who swings the renames)
        if dist.multiprocess_ring() is not None and dist.get_rank() != 0:
            return
        if jax.process_index() != 0:
            return
        from pytorch_distributed_tpu.train.checkpoint import (
            prune_checkpoints,
        )

        if self._async_ckpt is not None:
            # join the PREVIOUS save (started a ckpt interval ago, all but
            # certainly landed — near-zero block) so pruning can't race an
            # in-flight write; the UPCOMING save still overlaps training
            self._async_ckpt.wait()
        keep = max(cfg.keep_checkpoints - 1, 1)
        for path in prune_checkpoints(cfg.ckpt_dir, keep=keep):
            logger.info("pruned checkpoint: %s", path)

    def restore_checkpoint(self, tag: str = "latest") -> bool:
        """Restore the newest *intact* checkpoint for ``tag``.

        Walks ``restore_candidates`` newest→oldest — after recovering any
        directory a mid-swing kill stranded — skipping candidates whose
        manifest is unreadable or whose shards fail their recorded
        checksums, instead of crashing on the first bad one. Returns
        False when nothing checkpoint-shaped is on disk; raises
        ``CheckpointCorrupted`` when checkpoints exist for the default
        ``latest`` resume but every one of them is damaged (silently
        training from scratch would eventually overwrite the evidence).
        """
        with self._accounted("train.restore", "recovering", tag=tag):
            return self._restore_checkpoint_timed(tag)

    def _restore_checkpoint_timed(self, tag: str) -> bool:
        if self.config.ckpt_dir is None:
            return False
        from pytorch_distributed_tpu.train.checkpoint import (
            CheckpointCorrupted,
            recover_stranded_checkpoints,
            restore_candidates,
        )

        ckpt_dir = self.config.ckpt_dir
        # recovery renames directories: only the commit owner (who also
        # swings saves) may do it, and everyone else must not scan until
        # it is done — concurrent os.replace of the same dirs would race
        ring = dist.multiprocess_ring()
        if (
            ring is None or dist.get_rank() == 0
        ) and jax.process_index() == 0:
            recovered = recover_stranded_checkpoints(ckpt_dir)
            if recovered:
                logger.warning(
                    "recovered interrupted checkpoint commit(s): %s",
                    recovered,
                )
        if ring is not None and ring.world_size > 1:
            ring.barrier()
        from pytorch_distributed_tpu.train.checkpoint import _barrier

        _barrier("ptd_ckpt_recover")  # SPMD multi-host counterpart
        candidates = restore_candidates(ckpt_dir, tag)
        multi_ring = ring is not None and ring.world_size > 1
        multi_spmd = jax.process_count() > 1
        load_errors = []
        for cand in candidates:
            if not self._candidate_ok(
                ckpt_dir, cand, ring, multi_ring, multi_spmd
            ):
                continue  # verification failure, logged by the owner
            try:
                self._restore_state(cand)
            except Exception as e:
                if multi_ring or multi_spmd:
                    # a load failure only THIS process saw: falling back
                    # alone would split the world across two different
                    # checkpoints. Fail the whole job instead — the
                    # elastic restart retries every process consistently.
                    raise
                load_errors.append(e)
                logger.warning(
                    "restoring checkpoint %r failed (%s: %s) — falling "
                    "back to the next candidate",
                    cand, type(e).__name__, e,
                )
                continue
            self._resume_bookkeeping(cand)
            return True
        if load_errors:
            # every candidate that PASSED verification failed to load
            # into this state: a template/shape mismatch, not corruption
            # — surface the real error rather than quietly training fresh
            raise load_errors[0]
        if candidates:
            # candidates existed and every one was corrupt/skipped
            raise CheckpointCorrupted(
                f"checkpoints exist under {ckpt_dir!r} but none is "
                f"restorable — refusing to silently train from scratch"
            )
        # no readable candidates at all: distinguish 'nothing saved yet'
        # (clean fresh start / absent explicit tag) from 'the requested
        # checkpoints exist on disk with unreadable manifests'
        if tag == "latest":
            damaged = self._corrupt_checkpoints_present(ckpt_dir)
        else:
            damaged = any(
                os.path.isdir(os.path.join(ckpt_dir, n))
                for n in (tag, tag + ".old")
            )
        if damaged:
            raise CheckpointCorrupted(
                f"checkpoint directories for tag {tag!r} under "
                f"{ckpt_dir!r} exist but have unreadable manifests — "
                f"refusing to silently train from scratch"
            )
        return False

    def _candidate_ok(
        self, ckpt_dir, cand, ring, multi_ring, multi_spmd
    ) -> bool:
        """One candidate's intact/corrupt verdict, agreed across processes.

        Deep verification reads every shard — so in a multi-process
        world only the commit owner does it, and the verdict is
        broadcast: N hosts must NOT each re-read a multi-GB checkpoint,
        and (more importantly) all processes must skip the SAME
        candidates — a checksum failure only the owner noticed would
        otherwise split the world across two different checkpoints.
        Called lazily per fallback-loop iteration, so a clean resume
        verifies only the newest candidate, not the whole retention
        window.
        """
        from pytorch_distributed_tpu.train.checkpoint import (
            verify_checkpoint,
        )

        owner = (
            not multi_ring or dist.get_rank() == 0
        ) and jax.process_index() == 0
        ok = True
        if owner:
            problems = verify_checkpoint(ckpt_dir, cand)
            if problems:
                logger.warning(
                    "checkpoint %r failed verification (%s) — falling "
                    "back to the next candidate",
                    cand, "; ".join(problems[:3]),
                )
                ok = False
        vec = np.asarray([1.0 if ok else 0.0], np.float32)
        if multi_ring:
            ok = bool(ring.broadcast(vec, src=0)[0])
        elif multi_spmd:  # pragma: no cover - needs a real pod
            from jax.experimental import multihost_utils

            ok = bool(multihost_utils.broadcast_one_to_all(vec)[0])
        return ok

    @staticmethod
    def _corrupt_checkpoints_present(ckpt_dir: str) -> bool:
        """Any resume-shaped checkpoint dir (latest/step-*) on disk, even
        with an unreadable manifest? Distinguishes 'nothing saved yet'
        (fresh start is right) from 'everything saved is damaged' (fresh
        start destroys the evidence). ``.tmp`` dirs — an aborted FIRST
        save — do not count: there was never a complete checkpoint."""
        if not os.path.isdir(ckpt_dir):
            return False
        for name in os.listdir(ckpt_dir):
            base = name[:-len(".old")] if name.endswith(".old") else name
            if name.endswith(".tmp"):
                continue
            if base == "latest" or base.startswith("step-"):
                if os.path.isdir(os.path.join(ckpt_dir, name)):
                    return True
        return False

    def _restore_state(self, tag: str) -> None:
        """Load checkpoint ``tag`` into ``self.state`` (EMA-compatible)."""
        from pytorch_distributed_tpu.train.checkpoint import (
            restore_checkpoint,
        )

        try:
            self.state = restore_checkpoint(
                self.config.ckpt_dir,
                self.state,
                self.strategy.state_shardings(self.state),
                tag=tag,
            )
        except Exception as e:
            if self.state.ema_params is None or "ema_params" not in str(e):
                raise
            # checkpoint predates EMA: restore everything else, then seed
            # the shadow from the RESTORED params (seeding from the fresh
            # init template would track from random weights)
            template = self.state.replace(ema_params=None)
            restored = restore_checkpoint(
                self.config.ckpt_dir,
                template,
                self.strategy.state_shardings(template),
                tag=tag,
            )
            logger.warning(
                "checkpoint has no ema_params (pre-EMA run) — reseeding "
                "the shadow from the restored params"
            )
            self.state = restored.replace(
                ema_params=jax.tree_util.tree_map(
                    lambda x: jnp.array(x, dtype=jnp.float32, copy=True),
                    restored.params,
                )
            )

    def _resume_bookkeeping(self, tag: str) -> None:
        step = int(host_scalar(self.state.step))
        self.host_step = step
        from pytorch_distributed_tpu.train.checkpoint import (
            load_sampler_cursor,
        )

        cursor = load_sampler_cursor(self.config.ckpt_dir)
        if cursor is not None and cursor["step"] == step:
            # exact-batch resume: the persisted cursor replaces the
            # steps-per-epoch division (which cannot place bounded or
            # streaming loaders mid-epoch correctly). A cursor whose
            # offset equals a KNOWN epoch length (a cadence save that
            # landed exactly on the boundary) rolls to the next epoch —
            # replay-skipping a whole finished epoch of batch fetches
            # would waste an epoch of data loading on every resume.
            try:
                epoch_len = max(len(self.train_loader), 1)
                if self.config.max_steps_per_epoch:
                    epoch_len = min(
                        epoch_len, self.config.max_steps_per_epoch
                    )
            except TypeError:
                epoch_len = None  # stream: length unknowable, keep exact
            if epoch_len is not None and cursor["offset"] >= epoch_len:
                cursor = {
                    "step": step,
                    "epoch": cursor["epoch"] + 1,
                    "offset": 0,
                }
            self._first_epoch = cursor["epoch"]
            self._resume_skip_batches = cursor["offset"]
            self._cursor_epoch = cursor["epoch"]
            self._cursor_offset = cursor["offset"]
            self._load_best_record()
            logger.info(
                "resumed %r at step %d from the sampler cursor "
                "(epoch %d, skipping %d batches)",
                tag, step, self._first_epoch, self._resume_skip_batches,
            )
            return
        if cursor is not None:
            logger.warning(
                "sampler cursor on disk is for step %d but the restored "
                "checkpoint is step %d — ignoring it (falling back to "
                "the steps-per-epoch heuristic)", cursor["step"], step,
            )
        try:
            steps_per_epoch = max(len(self.train_loader), 1)
            if self.config.max_steps_per_epoch:
                steps_per_epoch = min(
                    steps_per_epoch, self.config.max_steps_per_epoch
                )
        except TypeError:
            if self.config.max_steps_per_epoch:
                # bounded stream: epochs are exactly max_steps_per_epoch
                # batches off a fresh pass, so the position IS
                # reconstructible — for a DETERMINISTIC stream that
                # yields at least that many batches per pass
                steps_per_epoch = self.config.max_steps_per_epoch
                if step % steps_per_epoch:
                    logger.warning(
                        "resuming a bounded stream mid-epoch: skipping "
                        "%d batches assumes the stream replays "
                        "deterministically — a reshuffling/live source "
                        "would lose that much fresh data",
                        step % steps_per_epoch,
                    )
            else:
                # streaming loader with unknown epoch length: the
                # epoch/offset position can't be reconstructed — resume
                # from the restored optimizer step at a fresh stream (the
                # torch IterableDataset resume story is the same)
                logger.warning(
                    "resumed a streaming loader at step %d: epoch "
                    "position unknown, restarting the stream from its "
                    "beginning", step,
                )
                self._first_epoch = 0
                self._resume_skip_batches = 0
                self._load_best_record()
                return
        self._first_epoch = step // steps_per_epoch
        # mid-epoch checkpoint: fast-forward past the batches this epoch
        # already consumed, so no batch trains twice and total step count
        # stays epochs * steps_per_epoch (LR schedules depend on it)
        self._resume_skip_batches = step % steps_per_epoch
        self._load_best_record()  # the pre-crash best must not be demoted
        logger.info(
            "resumed %r at step %d (epoch %d, skipping %d batches)",
            tag, step, self._first_epoch, self._resume_skip_batches,
        )

    # -- loops --------------------------------------------------------------
    def fit(self) -> TrainState:
        from pytorch_distributed_tpu.train import elastic

        cfg = self.config
        self._preemption = (
            elastic.PreemptionHandler().install()
            if cfg.handle_preemption else None
        )
        self._watchdog = (
            elastic.Watchdog(
                cfg.stall_timeout_s, on_stall=self._note_stall
            ).start()
            if cfg.stall_timeout_s else None
        )
        if cfg.trace and self._own_tracer is None:
            # re-arm for a second fit() — teardown disarmed the first
            self._own_tracer = tracing.configure(cfg.trace)
        try:
            for epoch in range(self._first_epoch, cfg.epochs):
                self.train_loader.set_epoch(epoch)
                self._train_epoch(epoch)
                # the epoch is consumed: a checkpoint written at this
                # boundary must resume at the NEXT epoch's first batch,
                # not replay-skip the finished one
                self._cursor_epoch = epoch + 1
                self._cursor_offset = 0
                if self.eval_step is not None and (
                    (epoch + 1) % cfg.eval_every_epochs == 0
                ):
                    means = self.evaluate(epoch)
                    if self._early_stop_triggered(means):
                        self.save_checkpoint()
                        logger.info(
                            "early stop at epoch %d: %s has not improved "
                            "for %d evals (best %s)", epoch,
                            cfg.keep_best, self._es_stale, self._es_best,
                        )
                        break
                self.save_checkpoint()
        finally:
            if getattr(self, "_tracing", False):
                # window ran past end of data (or training died inside
                # it). Best-effort: the drain touches device results and
                # re-raises a device failure — it must never mask the
                # original exception or starve the cleanups below.
                try:
                    host_scalar(self.state.step)
                except Exception:  # failed step: stop with what we have
                    pass
                try:
                    jax.profiler.stop_trace()
                except Exception:  # a broken trace must not mask the
                    pass           # original failure either
                self._tracing = False
                logger.warning(
                    "trace window %s outlived training (last step %d) — "
                    "trace includes end-of-epoch eval/checkpoint work",
                    cfg.trace_steps, self.host_step,
                )
            if self._async_ckpt is not None:
                self._async_ckpt.wait()  # last save must land before exit
            if self._preemption is not None:
                self._preemption.uninstall()
            if self._watchdog is not None:
                self._watchdog.stop()
            self._finish_observability()
            if self.metrics_writer is not None:
                self.metrics_writer.close()
        return self.state

    def _note_stall(self, idle_s: float) -> None:
        """Watchdog stall callback: the idle window is goodput-stalled
        time, and the stall lands on the trace timeline."""
        self._goodput.add("stalled", idle_s)
        tracing.instant(
            "watchdog.stall", idle_s=idle_s, step=self.host_step
        )

    @contextlib.contextmanager
    def _accounted(self, span_name: str, bucket: str, **span_args):
        """One shape for every attributed section: trace span + goodput
        bucket. A watchdog 'stall' that RESOLVES inside the section was
        a slow op, not a hang — its wall time is already covered by this
        section's own attribution, so the stalled seconds it accrued are
        retracted (buckets must keep summing to wall). A stall with no
        enclosing section (truly wedged loop) stands."""
        t0 = time.perf_counter()
        stalled0 = self._goodput.buckets.get("stalled", 0.0)
        try:
            with tracing.span(span_name, **span_args):
                yield
        finally:
            self._goodput.add(bucket, time.perf_counter() - t0)
            self._goodput.retract(
                "stalled",
                self._goodput.buckets.get("stalled", 0.0) - stalled0,
            )

    def _finish_observability(self) -> None:
        """End-of-fit accounting: goodput record + span rollups into the
        metrics stream, trace.json to cfg.trace. Best-effort — a broken
        export must never mask the original training exception."""
        try:
            if self.metrics_writer is not None:
                self.metrics_writer.write(
                    self.host_step,
                    {"event": "goodput", **self._goodput.summary()},
                    split="goodput",
                )
            if self._own_tracer is None:
                return
            if self.metrics_writer is not None:
                self._own_tracer.write_rollups(
                    self.metrics_writer, self.host_step
                )
            # one file per process: concurrent ranks writing one shared
            # trace dir must not swing over each other's export
            ring = dist.multiprocess_ring()
            rank = dist.get_rank() if ring is not None else jax.process_index()
            name = "trace.json" if rank == 0 else f"trace-rank{rank}.json"
            path = self._own_tracer.export(
                os.path.join(self.config.trace, name)
            )
            logger.info("span trace written to %s", path)
        except Exception:
            logger.exception("observability teardown failed (ignored)")
        finally:
            if self._own_tracer is not None:
                self._own_tracer = None
                tracing.clear()

    def _check_preemption(self) -> None:
        """Step-boundary poll: checkpoint and bail out on SIGTERM/SIGINT."""
        from pytorch_distributed_tpu.train import elastic

        if self._preemption is not None and self._preemption.requested:
            step = self.host_step
            self.save_checkpoint()
            if self._async_ckpt is not None:
                self._async_ckpt.wait()  # the restart will read it now
            logger.warning(
                "preemption checkpoint written at step %d — exiting for "
                "restart (resume restores from ckpt_dir)", step,
            )
            raise elastic.Preempted(step)

    def _measure_step_flops(self, batch) -> float:
        """Per-step FLOPs from XLA's own cost analysis (log_mfu).

        Lowering (a trace, no compile) is enough: ``Lowered.cost_analysis``
        prices the HLO without building an executable. Only if the backend
        can't price unoptimized HLO do we fall back to a real compile —
        which the persistent compilation cache (when enabled) turns into a
        disk hit. Any failure degrades to 0 (feature off) rather than
        interrupting training.

        Returns PER-DEVICE FLOPs (the MFU denominator ``peak_flops()`` is
        per-chip): the lowered path prices the unpartitioned global-shape
        HLO — whole-mesh work — so it is divided by device_count; the
        compiled path prices the per-device partitioned executable as-is.
        """
        from pytorch_distributed_tpu.runtime.device import compiled_flops

        try:
            lowered = self.train_step.lower(self.state, batch)
            flops = compiled_flops(lowered)
            if flops:
                flops /= jax.device_count()
            else:
                flops = compiled_flops(lowered.compile())
            return flops or 0.0
        except Exception as e:  # pragma: no cover - backend-specific
            logger.info("log_mfu disabled (cost analysis failed: %s)", e)
            return 0.0

    def _train_epoch(self, epoch: int) -> None:
        cfg = self.config
        t_last = time.perf_counter()
        steps_since_log = 0
        steps_since_sync = 0
        taken = 0
        capped = False
        skip = self._resume_skip_batches
        self._resume_skip_batches = 0
        self._cursor_epoch = epoch
        self._cursor_offset = 0
        it = iter(self.train_loader)
        while True:
            t_wait = time.perf_counter()
            with tracing.span("train.data_wait"):
                batch = next(it, _EPOCH_END)
            if batch is _EPOCH_END:
                break
            if (
                cfg.max_steps_per_epoch
                and taken >= cfg.max_steps_per_epoch
            ):  # bounds an epoch over an endless stream (IterableDataset)
                capped = True
                break
            taken += 1
            self._cursor_offset = taken  # batches consumed this epoch
            if skip > 0:
                skip -= 1
                # resume replay: consuming already-trained batches to
                # reach the checkpointed position is recovery time
                self._goodput.add(
                    "recovering", time.perf_counter() - t_wait
                )
                continue
            n = self._batch_samples(batch)
            if (
                cfg.log_mfu
                and self._step_flops is None
                and cfg.log_every
            ):  # all reporting (log line AND metrics-writer tflops) lives
                # inside the log_every block — never price an unused number
                self._step_flops = self._measure_step_flops(batch)
                t_last = time.perf_counter()  # don't bill the measurement
                # to the first logging window's step-time/MFU numbers
            self._trace_tick()
            with self._accounted("train.step", "productive"):
                self.state, metrics = self.train_step(self.state, batch)
            if tracing.active():
                # recompile sentinel: the jit cache of a steady-state
                # step must stop growing after warm-up
                tracing.note_compiles(
                    "train.step", jit_cache_size(self.train_step)
                )
            self.host_step += 1
            step = self.host_step
            if self._watchdog is not None:
                self._watchdog.tick(step)
            self._check_preemption()
            steps_since_log += 1
            steps_since_sync += 1
            if steps_since_sync >= 64:
                # Bound the async dispatch chain: with logging off (or a
                # huge log_every) nothing else syncs, and thousands of
                # donated steps queued unsynced abort the XLA runtime.
                # the drain blocks on queued step execution: productive
                with self._accounted("train.drain", "productive"):
                    jax.block_until_ready(metrics)
                steps_since_sync = 0
            if cfg.log_every and step % cfg.log_every == 0:
                # sync point: pull metrics (blocks on the step's result)
                with self._accounted("train.metric_fetch", "productive"):
                    metrics = {
                        k: host_scalar(v) for k, v in metrics.items()
                    }
                self._check_finite(metrics, step)
                now = time.perf_counter()
                dt = (now - t_last) / steps_since_log
                t_last = now
                steps_since_log = 0
                steps_since_sync = 0  # the host_scalar()s above just synced
                self.meter.update(MeterState(step_time=dt, samples_per_sec=n / dt))
                mfu_note = ""
                if self._step_flops:
                    from pytorch_distributed_tpu.runtime.device import (
                        peak_flops,
                    )

                    achieved = self._step_flops / dt
                    mfu_note = f" {achieved / 1e12:.1f} TFLOP/s"
                    # None on the CPU only; an accelerator missing from
                    # the peaks table raises rather than dropping the MFU
                    peak = peak_flops()
                    if peak is not None:
                        mfu_note += f" (mfu {achieved / peak * 100:.1f}%)"
                logger.info(
                    "epoch %d step %d %s %.1f samples/s (%.1f ms/step)%s",
                    epoch,
                    step,
                    " ".join(f"{k}={v:.4f}" for k, v in metrics.items()),
                    n / dt,
                    dt * 1e3,
                    mfu_note,
                )
                if self.metrics_writer is not None:
                    extra = {}
                    if self._step_flops:
                        extra["tflops"] = self._step_flops / dt / 1e12
                    extra["goodput_pct"] = round(
                        self._goodput.goodput_pct(), 2
                    )
                    if tracing.active():
                        # device memory gauge at log cadence (never on
                        # the step path): allocator stats where the
                        # backend has them, live-array sum otherwise
                        from pytorch_distributed_tpu.runtime.compat import (
                            live_buffer_bytes,
                        )

                        mem = live_buffer_bytes()
                        extra["device_bytes_in_use"] = mem
                        tracing.counter("device_bytes_in_use", mem)
                    self.metrics_writer.write(
                        step,
                        {**metrics, "samples_per_sec": n / dt,
                         "step_time_ms": dt * 1e3, "epoch": epoch, **extra},
                    )
            if cfg.ckpt_every_steps and step % cfg.ckpt_every_steps == 0:
                if cfg.keep_checkpoints:
                    self._prune_checkpoints()  # before the save: overlap
                    self.save_checkpoint(tag=f"step-{step}")
                else:
                    self.save_checkpoint()
        if (
            cfg.max_steps_per_epoch
            and not capped
            and taken < cfg.max_steps_per_epoch
            and getattr(self.train_loader, "iterable", False)
        ):
            logger.warning(
                "stream yielded only %d batches (< max_steps_per_epoch="
                "%d): resume epoch math assumes FULL epochs and would "
                "drift for this source",
                taken, cfg.max_steps_per_epoch,
            )

    def evaluate(self, epoch: int) -> Dict[str, float]:
        sums: Dict[str, float] = {}
        count = 0
        eval_state = self.state
        if self.config.eval_with_ema:
            if self.state.ema_params is None:
                raise ValueError(
                    "eval_with_ema needs shadow params: create the state "
                    "with TrainState.create(..., ema=True) and train with "
                    "build_train_step(ema_decay=...)"
                )
            eval_state = self.state.replace(params=self.state.ema_params)
        # eval is useful work, not overhead: productive in the goodput
        # account (its data wait rides along — the per-batch fetch syncs
        # dominate and already block on compute)
        with self._accounted("train.eval", "productive", epoch=epoch):
            for batch in self.eval_loader:
                metrics = self.eval_step(eval_state, batch)
                if self._watchdog is not None:
                    self._watchdog.tick()  # eval progress is progress
                n = self._batch_samples(batch)
                for k, v in metrics.items():
                    sums[k] = sums.get(k, 0.0) + host_scalar(v) * n
                count += n
        # multi-process mode: each rank saw 1/world of the eval set; sum
        # the weighted sums and counts over the ring so every rank reports
        # full-set metrics (reference DDP evals the full set too)

        ring = dist.multiprocess_ring()
        if ring is not None and ring.world_size > 1 and sums:
            keys = sorted(sums)
            vec = np.array([sums[k] for k in keys] + [float(count)],
                           np.float64)
            vec = ring.all_reduce(vec, op="sum")
            sums = dict(zip(keys, vec[:-1]))
            count = int(vec[-1])
        means = {k: v / max(count, 1) for k, v in sums.items()}
        if self.config.eval_finalize is not None:
            means = self.config.eval_finalize(means)
        self.last_eval_metrics = means
        logger.info(
            "eval epoch %d: %s",
            epoch,
            " ".join(f"{k}={v:.4f}" for k, v in means.items()),
        )
        if self.metrics_writer is not None:
            self.metrics_writer.write(
                self.host_step, {**means, "epoch": epoch}, split="eval"
            )
        self._maybe_save_best(means)
        return means

    def _trace_tick(self) -> None:
        """Start/stop the profiler at the configured host-step window.

        Runs BEFORE the step whose index matches, so [start, stop)
        captures exactly stop-start steps; the stop edge also syncs on
        the last traced step's result (stop_trace flushes only what has
        executed — without the sync the trace would be mostly dispatch).
        """
        cfg = self.config
        if cfg.trace_steps is None:
            return
        start, stop = cfg.trace_steps
        if not self._tracing and start <= self.host_step < stop:
            # range (not equality) so a resumed run landing inside the
            # window still captures its remainder
            jax.profiler.start_trace(cfg.trace_dir)
            self._tracing = True
        elif self._tracing and self.host_step >= stop:
            host_scalar(self.state.step)  # drain the traced steps
            jax.profiler.stop_trace()
            self._tracing = False
            logger.info(
                "profiler trace of steps [%d, %d) written to %s",
                start, stop, cfg.trace_dir,
            )

    def _check_finite(self, metrics: Dict[str, float], step: int) -> None:
        """Halt on persistently non-finite loss (halt_on_nonfinite).

        Checked only at the logging sync (no extra device fetches). The
        threshold is CONSECUTIVE logged occurrences: fp16's scaler can
        show transient inf while it searches for a scale, but NaN weights
        never heal — once the loss stays non-finite, every further step
        is wasted.
        """
        from pytorch_distributed_tpu.runtime import faults

        if faults.fires("step.nan"):
            # chaos site: divergence-on-demand, so halt_on_nonfinite's
            # restart path is provable without finding a real NaN recipe
            metrics["loss"] = float("nan")
        n = self.config.halt_on_nonfinite
        if not n or "loss" not in metrics:
            return
        if math.isfinite(metrics["loss"]):
            self._nonfinite_logs = 0
            return
        self._nonfinite_logs += 1
        logger.warning(
            "non-finite loss %s at step %d (%d/%d consecutive logs)",
            metrics["loss"], step, self._nonfinite_logs, n,
        )
        if self._nonfinite_logs >= n:
            raise TrainingDiverged(
                f"loss has been non-finite for {self._nonfinite_logs} "
                f"consecutive logging windows (last step {step}) — "
                "restart from the last finite checkpoint with a lower "
                "LR (set TrainerConfig(halt_on_nonfinite=0) to disable)"
            )

    def _improved(self, value: float, best: Optional[float]) -> bool:
        """One comparator for 'did the watched metric improve' — shared
        by best-checkpoint saving and early stopping so the two can
        never disagree about what counts as progress."""
        return (
            best is None
            or (self.config.best_mode == "max" and value > best)
            or (self.config.best_mode == "min" and value < best)
        )

    def _early_stop_triggered(self, means: Dict[str, float]) -> bool:
        cfg = self.config
        if cfg.early_stop_patience is None:
            return False
        value = means.get(cfg.keep_best)
        if value is None:
            # a metric evals never produce can never improve — stopping
            # "patiently" on a typo would silently truncate training
            raise ValueError(
                f"early-stop metric {cfg.keep_best!r} not in eval "
                f"metrics {sorted(means)}"
            )
        if not math.isfinite(value):
            # NaN cannot demonstrate improvement; count it as stale
            self._es_stale += 1
            return self._es_stale >= cfg.early_stop_patience
        if self._improved(value, self._es_best):
            self._es_best = value
            self._es_stale = 0
            return False
        self._es_stale += 1
        return self._es_stale >= cfg.early_stop_patience

    def _maybe_save_best(self, means: Dict[str, float]) -> None:
        """Save tag 'best' whenever the watched eval metric improves."""
        cfg = self.config
        if cfg.keep_best is None or cfg.ckpt_dir is None:
            return
        if cfg.keep_best not in means:
            logger.warning(
                "keep_best metric %r not in eval metrics %s — skipping",
                cfg.keep_best, sorted(means),
            )
            return
        value = means[cfg.keep_best]
        if not math.isfinite(value):
            # a NaN 'best' would win the first comparison and then beat
            # every later value (NaN compares False both ways), freezing
            # diverged weights under the 'best' tag forever
            return
        if self._improved(value, self._best_value):
            self._best_value = value
            self.save_checkpoint(tag="best")
            self._write_best_record(value)
            logger.info(
                "new best %s=%.4f (step %d)",
                cfg.keep_best, value, self.host_step,
            )

    def _best_record_path(self) -> str:
        return os.path.join(self.config.ckpt_dir, "best_metric.json")

    def _write_best_record(self, value: float) -> None:
        """Persist the best value so a resumed run can't demote 'best'."""
        if dist.multiprocess_ring() is not None and dist.get_rank() != 0:
            return
        if jax.process_index() != 0:
            return
        import json

        tmp = self._best_record_path() + ".tmp"
        with open(tmp, "w") as f:
            json.dump(
                {
                    "metric": self.config.keep_best,
                    "mode": self.config.best_mode,
                    "value": value,
                    "step": self.host_step,
                },
                f,
            )
        os.replace(tmp, self._best_record_path())

    def _load_best_record(self) -> None:
        cfg = self.config
        if cfg.keep_best is None or cfg.ckpt_dir is None:
            return
        import json

        try:
            with open(self._best_record_path()) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            return
        if rec.get("metric") == cfg.keep_best and rec.get("mode") == cfg.best_mode:
            self._best_value = rec.get("value")
            logger.info(
                "resumed best %s=%.4f (step %s)",
                cfg.keep_best, self._best_value, rec.get("step"),
            )

    def _batch_samples(self, batch) -> int:
        key = self.config.samples_axis
        if isinstance(batch, dict) and key in batch:
            return int(batch[key].shape[0])
        leaves = jax.tree_util.tree_leaves(batch)
        return int(leaves[0].shape[0]) if leaves else 0
