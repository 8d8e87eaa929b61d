"""Per-step pricing: comms volume x calibrated α–β model + compute term.

The comms side is deliberately the SAME arithmetic the tracer records
and the bench measures: payloads are priced through
``CostModel.predict``, which computes NCCL-convention wire bytes via
``hostring.algo_wire_bytes`` — the bytes the planner prices are the
bytes a ``comm.*`` span would record for the run it predicts. q8
gradient compression is priced at its REAL wire occupancy
(``hostring.q8_wire_payload``: int8 + one f32 scale per 256 elems,
~0.254x f32), so the candidate table shows the ~4x wire reduction as a
number, not a slogan.

Per-step collective volume per strategy class, per optimizer step
(accumulation microbatches share one gradient exchange by construction
— train/trainer.py scans them inside the jitted step):

=========  ==============================================================
dp (DDP)   1x all_reduce(grad_bytes) over the data axes
zero1      reduce_scatter(grads) + all_gather(updated params)
           (cross-replica weight-update sharding, arxiv 2004.13336)
fsdp       2x all_gather(params) [fwd + bwd re-gather] +
           reduce_scatter(grads), over the fsdp axis
tp (any)   4 x layers x all_reduce(per-device activation slab) over tp
           (Megatron f/g pairs, forward + backward)
=========  ==============================================================

With tp>1 the gradient payload is the per-tp-shard slice (each tp group
reduces only its own shard). Honest limits, also printed on the plan:
remat, overlap (compute/comms), and FSDP's per-layer pipelining are not
modeled — this prices serialized collectives, an upper bound that ranks
candidates correctly when they differ by volume or call count.

The compute term is flops / effective-flops, with effective flops
either calibrated from a measured step (``ComputeModel.from_measured_
step`` — the trainer's ``step`` span or bench history) or an assumed
per-platform default that marks the whole plan ``uncalibrated``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence

from pytorch_distributed_tpu.runtime.costmodel import CostModel
from pytorch_distributed_tpu.runtime.hostring import q8_wire_payload


@dataclasses.dataclass(frozen=True)
class ModelProfile:
    """What pricing needs to know about the model, beyond its param tree.

    ``flops_per_sample`` is the TRAIN step cost (forward + backward) per
    sample; ``activation_bytes_per_sample`` feeds the memory filter.
    ``layers``/``hidden``/``seq_len`` drive the tensor-parallel
    activation-collective terms; leave them 0 for models without a TP
    rule set (conv nets) and tp candidates simply price no tp comms.
    """

    flops_per_sample: float
    activation_bytes_per_sample: float
    layers: int = 0
    hidden: int = 0
    seq_len: int = 0
    act_dtype_bytes: int = 4


def transformer_profile(*, num_layers: int, hidden_size: int,
                        seq_len: int, param_count: int,
                        act_dtype_bytes: int = 4,
                        act_coeff: float = 16.0) -> ModelProfile:
    """Decoder-LM profile: 6·N flops per trained token (fwd 2N + bwd 4N,
    the PaLM/Chinchilla accounting), activations ≈ ``act_coeff`` x
    hidden slab per layer per token (~16 covers the block's
    residual/norm/attention/MLP intermediates without remat)."""
    return ModelProfile(
        flops_per_sample=6.0 * float(param_count) * seq_len,
        activation_bytes_per_sample=(
            float(num_layers) * seq_len * hidden_size
            * act_coeff * act_dtype_bytes
        ),
        layers=num_layers, hidden=hidden_size, seq_len=seq_len,
        act_dtype_bytes=act_dtype_bytes,
    )


def image_profile(*, flops_per_sample: float,
                  activation_bytes_per_sample: float) -> ModelProfile:
    """Conv-net profile: caller supplies the two totals (e.g. ResNet-50
    at 224²: ~3x4.1 GFLOPs trained, ~64 MB of f32 feature maps)."""
    return ModelProfile(
        flops_per_sample=float(flops_per_sample),
        activation_bytes_per_sample=float(activation_bytes_per_sample),
    )


#: assumed effective per-device flops when nothing measured is available
#: — deliberately conservative; using one marks the plan `uncalibrated`
ASSUMED_FLOPS_PER_S = {"cpu": 5e9, "tpu": 100e12, "gpu": 50e12}


@dataclasses.dataclass(frozen=True)
class ComputeModel:
    flops_per_s_per_device: float
    source: str  # "measured-step" | "assumed-<platform>"

    @property
    def calibrated(self) -> bool:
        return self.source == "measured-step"

    @classmethod
    def assumed(cls, platform: str) -> "ComputeModel":
        if platform not in ASSUMED_FLOPS_PER_S:
            # pricing an unknown accelerator at the CPU's rate would rank
            # every plan on a number nobody chose
            raise ValueError(
                f"no assumed FLOP/s for platform {platform!r} "
                f"(known: {sorted(ASSUMED_FLOPS_PER_S)}); pass a measured "
                f"ComputeModel or add the platform to ASSUMED_FLOPS_PER_S"
            )
        return cls(ASSUMED_FLOPS_PER_S[platform], f"assumed-{platform}")

    @classmethod
    def from_measured_step(cls, step_seconds: float, flops_per_step: float,
                           n_devices: int) -> "ComputeModel":
        """Effective flops from one measured reference step — folds the
        real MFU of this model on this backend into every candidate."""
        if step_seconds <= 0 or flops_per_step <= 0 or n_devices <= 0:
            raise ValueError("need positive step time, flops and devices")
        return cls(flops_per_step / n_devices / step_seconds,
                   "measured-step")


@dataclasses.dataclass
class CommTerm:
    """One collective in a candidate's step, priced."""

    op: str
    payload_bytes: int
    world: int
    count: int  # issues per step
    seconds: float = 0.0  # count x predicted per-call seconds
    wire_bytes: int = 0  # count x per-participant wire bytes
    extrapolated: bool = False
    note: str = ""
    #: for q8 terms: the f32 bytes the quantization REPLACED — the
    #: quantize/dequant passes sweep this domain, so the analytic
    #: quantize-cost term below prices against it, not the wire bytes
    f32_bytes: int = 0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


#: analytic quantize-cost passes for an UNCALIBRATED q8 fallback: the
#: native q8 ring (native/hostring.cpp) sweeps the f32 domain ~3x per
#: participant beyond the wire bytes (quantize the contribution,
#: dequant-accumulate the owned segment across peers, requantize +
#: dequant-copy the result), priced at the transport's own per-byte β.
#: Calibrated ON the measured shm numbers: at 6.4 MB / world 4 this
#: reproduces the recorded "q8 ~2x SLOWER than f32"
#: (runtime/hostring.py's measured trade-off) instead of the wire-bytes-
#: only model that predicted 0.25x — the mispricing that made
#: `--strategy auto` prefer a measured regression. A model with a real
#: all_reduce_q8 fit never uses this (the fit carries the true cost).
Q8_QUANTIZE_PASSES = 3.0


def q8_quantize_seconds(f32_bytes: int, beta_s_per_byte: float,
                        count: int = 1) -> float:
    """Analytic per-step quantize/dequant cost of a q8 collective whose
    f32 payload is ``f32_bytes`` — used ONLY when the cost model has no
    ``all_reduce_q8`` fit (which would already include it)."""
    return Q8_QUANTIZE_PASSES * float(f32_bytes) * beta_s_per_byte * count


def exposed_comm_seconds(comm_seconds: float,
                         overlappable_compute_seconds: float) -> float:
    """The round-14 overlap model: comm that fits under concurrently
    schedulable compute is hidden; only the excess extends the step.
    ``max(0, comm - overlappable)`` — an UPPER bound on hiding (perfect
    pipelining, no interference), the planner's usual serialized-bound
    honesty inverted, so candidates are compared by the same optimistic
    rule and the plan records which assumption priced them."""
    return max(0.0, float(comm_seconds)
               - float(overlappable_compute_seconds))


def grad_comm_terms(strategy: str, grad_payload_bytes: int,
                    grad_elems: int, data_world: int, *,
                    compress: Optional[str] = None) -> List[CommTerm]:
    """The gradient/param exchange for one optimizer step (table above)."""
    if data_world <= 1:
        return []
    if strategy == "dp":
        if compress == "int8":
            return [CommTerm("all_reduce_q8",
                             q8_wire_payload(grad_elems), data_world, 1,
                             note="q8 wire occupancy of the f32 grads",
                             f32_bytes=int(grad_payload_bytes))]
        return [CommTerm("all_reduce", grad_payload_bytes, data_world, 1)]
    if strategy == "zero1":
        return [
            CommTerm("reduce_scatter", grad_payload_bytes, data_world, 1),
            CommTerm("all_gather", grad_payload_bytes, data_world, 1,
                     note="updated params"),
        ]
    if strategy == "fsdp":
        return [
            CommTerm("all_gather", grad_payload_bytes, data_world, 2,
                     note="params, forward + backward re-gather"),
            CommTerm("reduce_scatter", grad_payload_bytes, data_world, 1),
        ]
    raise ValueError(f"unknown strategy class {strategy!r}")


def tp_comm_terms(profile: ModelProfile, micro_batch: int,
                  tp_world: int, accum_steps: int = 1) -> List[CommTerm]:
    """Megatron activation collectives: 4 all_reduce per layer per
    microbatch — an accumulating step pays them ``accum_steps`` times
    (same total volume as the unaccumulated step, more α calls)."""
    if tp_world <= 1 or profile.layers <= 0 or profile.hidden <= 0:
        return []
    slab = (micro_batch * max(profile.seq_len, 1) * profile.hidden
            * profile.act_dtype_bytes)
    return [CommTerm("all_reduce", int(slab), tp_world,
                     4 * profile.layers * max(accum_steps, 1),
                     note="tp activation slabs")]


def pipeline_comm_terms(profile: ModelProfile, micro_batch: int,
                        pp: int, num_microbatches: int) -> List[CommTerm]:
    """The r20 host-pipeline link traffic: every interior stage boundary
    moves one activation slab forward and one grad slab back per
    microbatch (``HostPipelineStep``'s tagged send/recv pairs). Priced
    at world=2 — the ordered P2P pair — and SERIALIZED (the planner's
    usual upper bound: the host loop issues them between compute ops,
    and on the steady-state critical path each link's transfers add
    up). Models without layer/hidden info (conv nets) price no pp
    links, same convention as :func:`tp_comm_terms`."""
    if pp <= 1 or profile.hidden <= 0:
        return []
    slab = (micro_batch * max(profile.seq_len, 1) * profile.hidden
            * profile.act_dtype_bytes)
    return [CommTerm(
        "send", int(slab), 2,
        2 * num_microbatches * (pp - 1),
        note="pp activation/grad handoffs (fwd + bwd per boundary)",
    )]


def pipeline_compute_split(
    profile: ModelProfile,
    global_batch: int,
    compute: ComputeModel,
    *,
    data: int,
    tp: int,
    pp: int,
    num_microbatches: int,
    stage_rates: Optional[Sequence[float]] = None,
):
    """(compute_seconds, bubble_seconds, stage_depths) for a pp
    candidate.

    The slowest stage's total work is the steady-state critical path:
    ``max over stages of (depth share / stage rate)`` applied to the
    per-(data x tp)-way flops. The warm-up/drain bubble adds
    ``(S-1)/M`` of that on top (the analytic ``(S-1)/(M+S-1)`` fraction
    of the whole step, bench-measurable from merged traces via
    ``parallel.pipeline_schedule.pipeline_trace_stats``). Homogeneous
    even splits reproduce the flat term exactly: ``max_stage =
    flops / (data*tp*pp) / rate``.

    ``stage_rates`` (one relative rate per stage: the MIN over the
    stage's device group — a stage's data ways commit in lockstep)
    makes the depth split the hetero apportionment
    (``pipeline_schedule.stage_depths`` -> ``train/balance.py``): a
    slow stage gets proportionally fewer layers, and the price reflects
    the discrete split the executor would actually build. Raises
    ValueError when ``profile.layers`` cannot fill/split the stages —
    the planner turns that into the candidate's infeasibility reason.
    """
    from pytorch_distributed_tpu.parallel.pipeline_schedule import (
        stage_depths,
    )

    if num_microbatches < 1:
        raise ValueError(
            f"num_microbatches must be >= 1, got {num_microbatches}"
        )
    layers = profile.layers
    if layers <= 0:
        raise ValueError(
            "pipeline candidates need profile.layers > 0 (the stage "
            "split is a layer split)"
        )
    rates = None
    if stage_rates is not None:
        rates = [float(r) for r in stage_rates]
        if len(set(rates)) == 1:
            rates = None  # homogeneous: use the even split
    depths = stage_depths(
        layers, pp,
        rank_rates=rates,
    )
    flops = profile.flops_per_sample * global_batch
    per_way = compute.flops_per_s_per_device * max(data, 1) * max(tp, 1)
    stage_seconds = [
        (flops * d / layers) / (per_way * (rates[s] if rates else 1.0))
        for s, d in enumerate(depths)
    ]
    slowest = max(stage_seconds)
    bubble = slowest * (pp - 1) / num_microbatches
    return slowest, bubble, depths


def price_comm_terms(terms: Sequence[CommTerm], model: CostModel,
                     fallback: Optional[CostModel] = None) -> List[CommTerm]:
    """Fill in seconds/wire_bytes/extrapolated from the cost model.

    Two degradation steps, both flagged in the term's note, never
    silent: q8 falls back to the plain all_reduce fit (β is a
    per-wire-byte transport property; the payload already carries the
    compression) when the model was never calibrated on
    ``all_reduce_q8``; any op the model has NO fit for at all (a
    partial calibration — ``collective_bench`` keeps later collectives
    running when one fails, so a model missing e.g. reduce_scatter is
    reachable) is priced on ``fallback`` (the planner passes the
    analytic guess) and marked ``extrapolated``. With no fallback the
    KeyError becomes an actionable :class:`CostModelUnavailable`.
    """
    from pytorch_distributed_tpu.runtime.costmodel import (
        CostModelUnavailable,
        calibration_command,
    )

    priced = []
    for t in terms:
        op = t.op
        note = t.note
        forced_extrapolated = False
        quantize_s = 0.0
        try:
            p = model.predict(op, t.payload_bytes, t.world)
        except KeyError:
            if op == "all_reduce_q8" and any(
                o == "all_reduce" for o, _ in model.fits
            ):
                p = model.predict("all_reduce", t.payload_bytes, t.world)
                # the wire-bytes-only fallback UNDERPRICED q8: on the
                # shm transport the quantize compute outweighs the byte
                # savings (measured ~2x slower — hostring.py). Add the
                # per-transport quantize-cost term at the fit's own β,
                # flagged: only a real q8 calibration removes the guess.
                quantize_s = q8_quantize_seconds(
                    t.f32_bytes, p.fit.beta_s_per_byte, t.count
                )
                forced_extrapolated = True
                note = (note + "; " if note else "") + (
                    "priced on the all_reduce fit (no q8 calibration) "
                    "+ analytic quantize cost "
                    f"(~{Q8_QUANTIZE_PASSES:g} f32 passes at the fit's "
                    "β)"
                )
            elif fallback is not None:
                p = fallback.predict(op, t.payload_bytes, t.world)
                forced_extrapolated = True
                note = (note + "; " if note else "") + (
                    f"priced analytically ({op} missing from the "
                    f"calibrated model)"
                )
            else:
                raise CostModelUnavailable(
                    f"cost model ({model.transport}) has no fit for "
                    f"{op!r} and no fallback — recalibrate: "
                    f"`{calibration_command()}`"
                ) from None
        priced.append(dataclasses.replace(
            t,
            seconds=p.seconds * t.count + quantize_s,
            wire_bytes=p.wire_bytes * t.count,
            extrapolated=p.extrapolated or forced_extrapolated,
            note=note,
        ))
    return priced


def compute_seconds(profile: ModelProfile, global_batch: int,
                    n_devices: int, compute: ComputeModel) -> float:
    """Per-step compute: total trained flops over the fleet's effective
    rate (tp/fsdp partition the same flops across devices; their
    efficiency loss is not modeled — see module docstring)."""
    flops = profile.flops_per_sample * global_batch
    return flops / max(n_devices, 1) / compute.flops_per_s_per_device


def hetero_compute_seconds(
    profile: ModelProfile,
    global_batch: int,
    compute: ComputeModel,
    rank_rates: Sequence[float],
    *,
    tp: int = 1,
    microshards: Optional[int] = None,
    balanced: bool = True,
) -> float:
    """Per-step compute on a MIXED-SPEED fleet: the step commits when
    the slowest rank finishes, so the term is ``max over data ways of
    (assigned work / way rate)`` — the r15 balancing model
    (train/balance.py), priced with the engine's OWN discrete
    apportionment so the plan reproduces what the balancer will
    actually assign, quantization and all.

    ``rank_rates`` are RELATIVE per-device speed multipliers on
    ``compute.flops_per_s_per_device`` (1.0 = nominal, 0.5 = half
    speed). With ``tp > 1`` consecutive devices form one tp group that
    computes in lockstep, so a way's rate is the MIN over its members —
    mixing speeds inside a tp group wastes the fast members, and the
    price says so. ``balanced=False`` prices the even split (the
    balance=off baseline; its max is governed by the slowest way);
    ``balanced=True`` prices the proportional split over ``microshards``
    units (default ``MIN_SHARDS_PER_RANK x ways`` — the granularity
    floor ``train/balance.granularity_ok`` warns below).
    """
    from pytorch_distributed_tpu.train.balance import (
        MIN_SHARDS_PER_RANK,
        apportion,
        counts_of,
        even_assignment,
        quantize_rates,
    )

    n = len(rank_rates)
    tp = max(int(tp), 1)
    if n % tp:
        raise ValueError(
            f"{n} device rate(s) do not form tp={tp} groups"
        )
    ways = [
        min(float(r) for r in rank_rates[g * tp:(g + 1) * tp])
        for g in range(n // tp)
    ]
    D = len(ways)
    flops = profile.flops_per_sample * global_batch
    S = int(microshards) if microshards else MIN_SHARDS_PER_RANK * D
    if balanced and S >= D:
        counts = apportion(S, quantize_rates(ways), floor=1)
    else:
        counts = counts_of(even_assignment(S, D), D)
    per_way_flops_per_s = compute.flops_per_s_per_device * tp
    return max(
        (flops * c / S) / (per_way_flops_per_s * r)
        for c, r in zip(counts, ways)
    )


def wire_ratio(terms_a: Sequence[CommTerm],
               terms_b: Sequence[CommTerm]) -> float:
    """Total-wire-bytes ratio a/b — the q8-vs-f32 comparison number."""
    a = sum(t.wire_bytes for t in terms_a)
    b = sum(t.wire_bytes for t in terms_b)
    return a / b if b else math.inf


@dataclasses.dataclass(frozen=True)
class HierPrice:
    """A priced hierarchical allreduce (runtime/hierarchy.py): the three
    sequential legs, each on its own transport's fit."""

    intra_reduce_s: float
    inter_exchange_s: float
    intra_bcast_s: float
    #: per-leader bytes over the slow link — 2(H-1)/H x payload for the
    #: f32 leg (q8 inter shrinks the payload first); THE number the
    #: bench multihost phase verifies against the measured counter
    inter_wire_bytes: int
    extrapolated: bool
    terms: List[CommTerm] = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return (self.intra_reduce_s + self.inter_exchange_s
                + self.intra_bcast_s)

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["seconds"] = self.seconds
        d["terms"] = [t.to_dict() for t in self.terms]
        return d


def hierarchical_allreduce_seconds(
    payload_bytes: int,
    grad_elems: int,
    domain_sizes: Sequence[int],
    intra_model: CostModel,
    inter_model: CostModel,
    *,
    q8_inter: bool = False,
    fallback: Optional[CostModel] = None,
) -> HierPrice:
    """Price one hierarchical allreduce: intra-domain reduce -> one
    inter-domain leader exchange -> intra-domain broadcast
    (``runtime/hierarchy.py``'s decomposition), each leg on ITS OWN
    transport's α–β fit — the per-transport discipline
    ``CostModel.load(expected_transport=...)`` enforces is exactly what
    makes this sum meaningful (an shm β under the inter leg would
    underprice the slow link ~an order of magnitude).

    The legs are sequential (a leg cannot start before the previous
    completes), so the total is their SUM; within a leg every domain
    runs concurrently, so each leg's price is the MAX over its domains'
    sizes (equal-size domains — the only shape the group supports for
    all_gather — collapse to one prediction). ``q8_inter=True`` prices
    the quantized inter leg at its real wire occupancy
    (``q8_wire_payload``), falling back through
    :func:`price_comm_terms`'s flagged q8 path when the inter model has
    no ``all_reduce_q8`` fit. Degenerate shapes price honestly: one
    domain -> no inter leg; all domains singleton -> only the inter leg.
    """
    doms = [int(d) for d in domain_sizes]
    if not doms or any(d < 1 for d in doms):
        raise ValueError(f"bad domain sizes {domain_sizes!r}")
    H = len(doms)

    def leg_max(op: str, note: str) -> List[CommTerm]:
        sizes = sorted({d for d in doms if d > 1})
        terms = price_comm_terms(
            [CommTerm(op, int(payload_bytes), d, 1, note=note)
             for d in sizes],
            intra_model, fallback=fallback,
        )
        return terms

    intra_reduce = leg_max("all_reduce", "hier intra reduce")
    intra_bcast = leg_max("broadcast", "hier intra broadcast")
    inter_terms: List[CommTerm] = []
    if H > 1:
        if q8_inter:
            t = CommTerm("all_reduce_q8", q8_wire_payload(int(grad_elems)),
                         H, 1, note="hier inter exchange (q8)",
                         f32_bytes=int(payload_bytes))
        else:
            t = CommTerm("all_reduce", int(payload_bytes), H, 1,
                         note="hier inter exchange")
        inter_terms = price_comm_terms([t], inter_model,
                                       fallback=fallback)
    all_terms = intra_reduce + inter_terms + intra_bcast
    return HierPrice(
        intra_reduce_s=max((t.seconds for t in intra_reduce), default=0.0),
        inter_exchange_s=sum(t.seconds for t in inter_terms),
        intra_bcast_s=max((t.seconds for t in intra_bcast), default=0.0),
        inter_wire_bytes=sum(t.wire_bytes for t in inter_terms),
        extrapolated=any(t.extrapolated for t in all_terms),
        terms=all_terms,
    )
