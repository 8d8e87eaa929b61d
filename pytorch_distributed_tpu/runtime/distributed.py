"""Process-group-shaped facade over XLA collectives.

The reference's recipes call ``torch.distributed.init_process_group('nccl')``
then use rank-centric collectives (BASELINE.json:5). Under single-controller
SPMD there are no ranks — one Python process drives every chip, and
collectives are compiler-inserted ops over the mesh. This module keeps the
*texture* of that API so recipe scripts read like the originals, with honest
single-controller semantics:

* ``init_process_group`` builds the device mesh ("the world") and picks a
  backend: ``"ici"`` — XLA collectives over ICI/DCN on TPU (the NCCL
  equivalent), ``"gloo"``/``"cpu"`` — the same XLA collectives on host CPU
  devices (smoke-test path, matching the reference's gloo recipe,
  BASELINE.json:7).
* Eager collectives (``all_reduce`` & co) take an array whose leading
  dimension is the participant axis — "each participant's tensor" — and
  reduce/gather across it on-device via ``shard_map``. Inside a jitted
  step you don't call these: you call ``jax.lax.psum`` et al. directly (or
  let sharding propagation insert them).
* ``get_rank()`` is the controller process index (0 on a single host) —
  used by recipes only to gate logging/checkpointing, which is exactly what
  it still means here.
* ``backend="hostring"`` — the genuine multi-process path: when launched
  one-process-per-rank (``pytorch_distributed_tpu.run`` / ``spawn``, the
  torchrun/mp.spawn texture of BASELINE.json:5), ranks rendezvous over the
  native shared-memory collectives library (``native/hostring.cpp``, the
  gloo equivalent) and the eager collectives below take *this rank's local
  tensor* — exact torch.distributed semantics. Selected automatically when
  ``RANK``/``WORLD_SIZE`` env vars are present (set by the launcher).
"""

from __future__ import annotations

import dataclasses
import enum
import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_distributed_tpu.runtime import device as _device
from pytorch_distributed_tpu.runtime import mesh as _mesh


class ReduceOp(enum.Enum):
    SUM = "sum"
    AVG = "avg"
    MAX = "max"
    MIN = "min"
    PRODUCT = "product"


@dataclasses.dataclass
class ProcessGroup:
    mesh: Mesh
    backend: str
    ring: Optional[object] = None  # HostRingGroup in multi-process mode
    ring_name: Optional[str] = None  # the ring's shm name (subgroup prefix)

    @property
    def size(self) -> int:
        if self.ring is not None:
            return self.ring.world_size
        return int(np.prod(list(self.mesh.shape.values())))


_GROUP: Optional[ProcessGroup] = None
_INIT_GENERATION = 0  # per-init shm-name suffix; see hostring re-init guard

_BACKENDS = ("ici", "cpu")


def init_process_group(
    backend: Optional[str] = None,
    *,
    mesh_spec: Optional[_mesh.MeshSpec] = None,
    world_size: Optional[int] = None,
    rank: Optional[int] = None,
    group_name: Optional[str] = None,
    timeout_s: float = 120.0,
) -> ProcessGroup:
    """Create the global "world": a mesh over all addressable devices.

    ``backend=None`` auto-selects ``"ici"`` on TPU and ``"cpu"`` otherwise.
    ``world_size`` may restrict to the first N devices (smoke tests).

    When this process was launched one-per-rank (``rank`` given, or
    ``RANK``/``WORLD_SIZE`` in the env — the launcher sets them), the group
    joins the native shared-memory backend instead: real multi-process
    collectives, matching the reference's gloo smoke path.
    """
    global _GROUP
    if rank is None and "RANK" in os.environ:
        rank = int(os.environ["RANK"])
    # Multi-host (pod) launch: one controller per host. Rendezvous first so
    # jax.devices() spans the pod, then fall through to the single-controller
    # path — RANK here is the host index, not a per-device rank.
    if os.environ.get("PTD_MULTIHOST") == "1":
        _ensure_multihost_init()
        rank = None
    if backend == "hostring" or (
        rank is not None and backend in (None, "gloo", "cpu")
    ):
        from pytorch_distributed_tpu.runtime.hostring import HostRingGroup

        if world_size is None and "WORLD_SIZE" in os.environ:
            world_size = int(os.environ["WORLD_SIZE"])
        if world_size is None:
            raise ValueError("multi-process init needs world_size (or env)")
        if rank is None:
            raise ValueError(
                "multi-process init needs this process's rank (arg or RANK "
                "env) — every rank defaulting to 0 would corrupt the group"
            )
        if mesh_spec is not None:
            # Recipes pass MeshSpec(dp=-1) unconditionally; under the
            # launcher each rank drives ONE device, so specs that resolve
            # to a single device are fine (wildcards collapse to 1). Only
            # an explicit multi-device request is a conflict.
            if any(s > 1 for s in mesh_spec.sizes()):
                raise ValueError(
                    f"mesh_spec {mesh_spec} requests multiple devices but "
                    "this process was launched one-rank-per-process "
                    "(RANK/WORLD_SIZE set): each rank drives one device. "
                    "Unset RANK/WORLD_SIZE to run single-controller SPMD "
                    "with a mesh."
                )
        if _GROUP is not None and _GROUP.ring is not None:
            _GROUP.ring.close()  # re-init: release the old shm membership
        if group_name is None:
            # the launcher hands every worker a per-rendezvous group name
            group_name = os.environ.get("PTD_GROUP_NAME", "ptd_world")
        # Re-init race guard: after close(), a fast peer's fresh hr_init
        # could attach the OLD segment before rank 0 unlinks/recreates it
        # (its magic is still set), splitting the group until timeout. A
        # per-init generation suffix gives every rendezvous a fresh shm
        # name; all ranks tear down and re-init in lockstep (collectives
        # are group-wide), so the counter stays in step across processes.
        global _INIT_GENERATION
        _INIT_GENERATION += 1
        ring_name = f"{group_name}_g{_INIT_GENERATION}"
        # clock_sync: the WORLD ring measures per-rank wall-clock offsets
        # at init (barrier handshake) and stamps them into the trace
        # metadata so scripts/trace_merge.py can align per-rank
        # timelines. Subgroups skip it — their ranks are renumbered and
        # the world's offsets already cover every process.
        ring = HostRingGroup(
            ring_name, rank, world_size, timeout_s=timeout_s,
            clock_sync=True,
        )
        # Each rank still gets a local 1-device mesh so jit/sharding code
        # paths work unchanged within the rank.
        mesh = _mesh.make_mesh(
            _mesh.MeshSpec(dp=1), devices=jax.devices("cpu")[:1]
        )
        _GROUP = ProcessGroup(
            mesh=mesh, backend="hostring", ring=ring, ring_name=ring_name
        )
        return _GROUP
    if backend in (None, "nccl", "xla"):
        # Reference recipes say init_process_group('nccl') (BASELINE.json:5)
        # and the torch-xla port spelling is 'xla'; the TPU equivalent of
        # both fast paths is XLA collectives over ICI. The host is used
        # only when the caller pinned JAX to it — a missing TPU is an
        # error, not a quiet CPU run (pass backend='cpu' to mean the host)
        backend = {"tpu": "ici", "cpu": "cpu"}[
            _device.require_tpu_or_requested_cpu()
        ]
    elif backend == "gloo":
        backend = "cpu"
    if backend not in _BACKENDS:
        raise ValueError(f"Unknown backend {backend!r}; expected one of {_BACKENDS}")
    if backend == "ici" and not _device.is_tpu():
        raise RuntimeError(
            "backend='ici' requires TPU devices; use 'cpu' (gloo-equivalent) "
            "for the host smoke path"
        )
    # The cpu/gloo path asks the CPU backend for its devices explicitly:
    # the default platform may be TPU, but jax.devices("cpu") still yields
    # the host devices, honouring --xla_force_host_platform_device_count.
    devices = jax.devices("cpu") if backend == "cpu" else jax.devices()
    if world_size is not None:
        if world_size > len(devices):
            raise ValueError(f"world_size {world_size} > {len(devices)} devices")
        devices = devices[:world_size]
    mesh = _mesh.make_mesh(mesh_spec, devices=devices)
    _GROUP = ProcessGroup(mesh=mesh, backend=backend)
    return _GROUP


_MULTIHOST_DONE = False


def _ensure_multihost_init() -> None:
    global _MULTIHOST_DONE
    if not _MULTIHOST_DONE:
        from pytorch_distributed_tpu.launch import init_multihost

        init_multihost()
        _MULTIHOST_DONE = True


def rebuild_process_group(
    *,
    ring=None,
    mesh_spec: Optional[_mesh.MeshSpec] = None,
    world_size: Optional[int] = None,
) -> ProcessGroup:
    """Re-mesh the world IN PROCESS — the elastic resize path.

    Where ``destroy_process_group`` + ``init_process_group`` is the
    die-and-restore shape (everything rebuilt from scratch), this swaps
    only what a membership change invalidates and keeps the process —
    its jit caches, host state, and page cache — alive:

    * ``ring=...`` (hostring backend): adopt an already-committed epoch
      ring from :class:`runtime.membership.WorldMembership` — the old
      ring is closed, open subgroups (which indexed the OLD rank space)
      are closed, and the rank-local 1-device mesh is kept.
    * ``mesh_spec``/``world_size`` (single-controller SPMD): rebuild the
      mesh over the surviving device set via :func:`runtime.mesh.remesh`
      (e.g. a pod slice shrank); callers then re-place state through the
      Strategy / checkpoint machinery.

    Raises unless a group already exists — rebuilding nothing is a
    caller bug, not a bootstrap path.
    """
    global _GROUP
    if _GROUP is None:
        raise RuntimeError(
            "rebuild_process_group needs a live group; call "
            "init_process_group first"
        )
    for sub in _SUBGROUPS:  # subgroup ranks indexed the old world
        sub.close()
    _SUBGROUPS.clear()
    _collective.cache_clear()
    if ring is not None:
        if _GROUP.ring is not None and _GROUP.ring is not ring:
            _GROUP.ring.close()
        _GROUP = ProcessGroup(
            mesh=_GROUP.mesh, backend="hostring", ring=ring,
            ring_name=getattr(ring, "name", None),
        )
        return _GROUP
    if _GROUP.ring is not None:
        raise ValueError(
            "hostring groups rebuild around a committed membership "
            "ring; pass ring=..."
        )
    devices = list(_GROUP.mesh.devices.flat)
    if world_size is not None:
        if world_size > len(devices):
            raise ValueError(
                f"world_size {world_size} > {len(devices)} devices in "
                "the current mesh — a grown device set needs a fresh "
                "init_process_group"
            )
        devices = devices[:world_size]
    mesh = _mesh.remesh(mesh_spec, devices=devices)
    _GROUP = ProcessGroup(mesh=mesh, backend=_GROUP.backend)
    return _GROUP


def multiprocess_ring():
    """The HostRingGroup when running one-process-per-rank, else None.

    The public accessor for "is this the true multi-process path" — data
    loaders, samplers, and the DDP grad sync all key off it.
    """
    g = _GROUP
    return g.ring if g is not None else None


def destroy_process_group() -> None:
    global _GROUP
    for sub in _SUBGROUPS:  # torch destroys all groups, not just the world
        sub.close()
    _SUBGROUPS.clear()
    if _GROUP is not None and _GROUP.ring is not None:
        _GROUP.ring.close()
    _GROUP = None
    _mesh.set_current_mesh(None)
    _collective.cache_clear()


def is_initialized() -> bool:
    return _GROUP is not None


def _group() -> ProcessGroup:
    if _GROUP is None:
        init_process_group()
    return _GROUP  # type: ignore[return-value]


_SUBGROUP_SEQ = 0
_SUBGROUPS: list = []  # open subgroups; destroy_process_group closes them


class Subgroup:
    """Handle from :func:`new_group` — collectives over a rank subset.

    ``ring`` is a member-only dedicated shm ring under the hostring
    backend; single-controller SPMD needs no extra state (subgroup
    collectives select the member rows of the participant dim).
    """

    def __init__(self, ranks, *, ring=None, member: bool):
        self.ranks = ranks
        self.ring = ring
        self.is_member = member

    @property
    def size(self) -> int:
        return len(self.ranks)

    def close(self) -> None:
        if self.ring is not None:
            self.ring.close()
            self.ring = None


def new_group(ranks, *, timeout_s: float = 60.0) -> Subgroup:
    """``torch.distributed.new_group``: a subgroup of the world.

    torch's contract carries over: EVERY process must call ``new_group``
    with the same ``ranks`` in the same order (bystanders included —
    under the hostring backend the call sequence number names the
    subgroup's shm segment, so out-of-order creation would cross-wire
    groups). Member ranks of a hostring world rendezvous a dedicated shm
    ring; bystanders get a handle whose collectives refuse loudly. Under
    single-controller SPMD any process may use the handle — a subgroup
    collective reduces/gathers only the member rows of the leading
    participant dim.
    """
    global _SUBGROUP_SEQ
    g = _group()
    rs = tuple(sorted(int(r) for r in ranks))
    if not rs:
        raise ValueError("new_group needs at least one rank")
    if len(set(rs)) != len(rs):
        raise ValueError(f"ranks must be unique, got {rs}")  # like torch —
        # silently deduplicating would mask a buggy rank list (AVG would
        # divide by the wrong size)
    if rs[0] < 0 or rs[-1] >= g.size:
        raise ValueError(f"ranks {rs} out of range for world size {g.size}")
    _SUBGROUP_SEQ += 1
    if g.ring is not None:
        member = g.ring.rank in rs
        ring = None
        if member:
            from pytorch_distributed_tpu.runtime.hostring import (
                HostRingGroup,
            )

            # prefixed with the WORLD ring's per-launch/per-generation shm
            # name: concurrent launches can't cross-wire, and the
            # launcher's teardown glob ('<name>_g*') reaps crashed
            # subgroup segments along with the world's
            name = (
                f"{g.ring_name}_sub{_SUBGROUP_SEQ}_"
                + "_".join(map(str, rs))
            )
            ring = HostRingGroup(
                name, rs.index(g.ring.rank), len(rs), timeout_s=timeout_s
            )
        sub = Subgroup(rs, ring=ring, member=member)
        _SUBGROUPS.append(sub)
        return sub
    sub = Subgroup(rs, member=True)
    _SUBGROUPS.append(sub)
    return sub


def _subgroup_rows(x, group: Subgroup):
    x = jnp.asarray(x)
    if x.shape[0] != _group().size:
        raise ValueError(
            f"subgroup collectives take the FULL participant dim "
            f"(world={_group().size}), got leading dim {x.shape[0]}"
        )
    return x[jnp.asarray(group.ranks)]


def _require_member(group: Subgroup, what: str):
    if not group.is_member:
        raise RuntimeError(
            f"{what} on a subgroup this rank is not a member of "
            f"(ranks={group.ranks})"
        )
    if group.ring is None:
        raise RuntimeError(f"{what} on a closed subgroup")


def _no_axis_with_group(axis):
    if axis is not None:
        raise ValueError(
            "axis and group are mutually exclusive: subgroup ranks index "
            "the flattened world, not a mesh axis"
        )


_SUB_REDUCE = {
    ReduceOp.SUM: jnp.sum,
    ReduceOp.AVG: jnp.mean,
    ReduceOp.MAX: jnp.max,
    ReduceOp.MIN: jnp.min,
    ReduceOp.PRODUCT: jnp.prod,
}


def get_world_size() -> int:
    """Total devices in the world — the SPMD analogue of nranks."""
    return _group().size


def get_rank() -> int:
    """Controller process index; gates logging/checkpoint like rank==0.

    Under the hostring (multi-process) backend this is the real rank."""
    g = _GROUP
    if g is not None and g.ring is not None:
        return g.ring.rank
    return _device.process_index()


def get_backend() -> str:
    return _group().backend


# --------------------------------------------------------------------------
# Eager collectives.
#
# Convention: the input's leading dimension indexes participants (size must
# equal the product of the mesh axes being reduced over). This is the
# single-controller translation of "every rank passes its tensor".
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=256)
def _collective(kind: str, op: ReduceOp, axes: tuple, mesh: Mesh):
    in_spec = P(axes)

    def reduce_fn(v):  # v: this participant's tensor (leading dim stripped)
        if op is ReduceOp.SUM:
            return lax.psum(v, axes)
        if op is ReduceOp.AVG:
            return lax.pmean(v, axes)
        if op is ReduceOp.MAX:
            return lax.pmax(v, axes)
        if op is ReduceOp.MIN:
            return lax.pmin(v, axes)
        if op is ReduceOp.PRODUCT:
            g = lax.all_gather(v, axes)  # [participants, ...]
            return jnp.prod(g, axis=0)
        raise ValueError(op)

    if kind == "all_reduce":

        def f(x):  # x: [1, ...] per-shard slice of the participant dim
            return reduce_fn(x[0])

        out_spec = P()
    elif kind == "all_to_all":

        def f(x):
            # participant p sends chunk j of its [W*c, ...] row to j and
            # concatenates what it receives — torch all_to_all_single
            return lax.all_to_all(
                x[0], axes, split_axis=0, concat_axis=0, tiled=True
            )[None]

        out_spec = P(axes)
    elif kind == "permute":
        # op smuggles the perm tuple (hashable) through the lru_cache key
        perm = op

        def f(x):
            return lax.ppermute(x, axes, perm=perm)

        out_spec = P(axes)
    elif kind == "all_gather":

        def f(x):
            return lax.all_gather(x, axes, tiled=True)

        out_spec = P()
    elif kind == "reduce_scatter":

        def f(x):
            # x per-shard: [1, participants * chunk, ...]; sum across
            # participants, each keeps its chunk -> global result is the
            # reduced vector, sharded over the axis.
            return lax.psum_scatter(x[0], axes, scatter_dimension=0, tiled=True)

        out_spec = P(axes)
    else:
        raise ValueError(kind)

    fn = jax.shard_map(
        f, mesh=mesh, in_specs=(in_spec,), out_specs=out_spec, check_vma=False
    )
    return jax.jit(fn)


def _participant_axes(axis) -> tuple:
    if axis is None:
        return tuple(a for a in _mesh.AXES)
    if isinstance(axis, str):
        return (axis,)
    return tuple(axis)


def _check_leading(x, axes, mesh) -> int:
    size = int(np.prod([mesh.shape[a] for a in axes]))
    if x.shape[0] != size:
        raise ValueError(
            f"leading dim {x.shape[0]} must equal participant count {size} "
            f"for axes {axes}"
        )
    return size


def all_reduce(x, op: ReduceOp = ReduceOp.SUM, *, axis=None, group=None):
    """Reduce across the leading (participant) dim; returns shape x[0].

    ``axis=None`` reduces over the whole mesh. Under the hostring backend
    ``x`` is this rank's local tensor (torch semantics) and the result has
    the same shape. ``group`` (from :func:`new_group`) restricts the
    collective to a rank subset.
    """
    g = _group()
    if group is not None:
        _no_axis_with_group(axis)
        if g.ring is not None:
            _require_member(group, "all_reduce")
            return jnp.asarray(
                group.ring.all_reduce(np.asarray(x), op=op.value)
            )
        return _SUB_REDUCE[op](_subgroup_rows(x, group), axis=0)
    if g.ring is not None:
        return jnp.asarray(g.ring.all_reduce(np.asarray(x), op=op.value))
    axes = _participant_axes(axis)
    x = jnp.asarray(x)
    _check_leading(x, axes, g.mesh)
    fn = _collective("all_reduce", op, axes, g.mesh)
    return fn(jax.device_put(x, NamedSharding(g.mesh, P(axes))))


def all_gather(x, *, axis=None, group=None):
    """Gather participant slices; identity values, replicated layout.

    Under hostring: gathers each rank's local tensor into [world, ...].
    With ``group``: [len(group.ranks), ...] in member order."""
    g = _group()
    if group is not None:
        _no_axis_with_group(axis)
        if g.ring is not None:
            _require_member(group, "all_gather")
            return jnp.asarray(group.ring.all_gather(np.asarray(x)))
        return _subgroup_rows(x, group)
    if g.ring is not None:
        return jnp.asarray(g.ring.all_gather(np.asarray(x)))
    axes = _participant_axes(axis)
    x = jnp.asarray(x)
    _check_leading(x, axes, g.mesh)
    fn = _collective("all_gather", ReduceOp.SUM, axes, g.mesh)
    return fn(jax.device_put(x, NamedSharding(g.mesh, P(axes))))


def reduce_scatter(x, op: ReduceOp = ReduceOp.SUM, *, axis=None):
    """Reduce across participants, scatter chunks of dim 1 back over them.

    Input: [participants, participants * chunk, ...] — returns the
    reduced array of shape [participants * chunk, ...], sharded over the axis.
    """
    if op is not ReduceOp.SUM:
        raise NotImplementedError("reduce_scatter supports SUM")
    g = _group()
    if g.ring is not None:
        return jnp.asarray(g.ring.reduce_scatter(np.asarray(x), op="sum"))
    axes = _participant_axes(axis)
    x = jnp.asarray(x)
    _check_leading(x, axes, g.mesh)
    fn = _collective("reduce_scatter", op, axes, g.mesh)
    return fn(jax.device_put(x, NamedSharding(g.mesh, P(axes))))


def all_gather_into_tensor(x, *, axis=None, group=None):
    """torch >= 1.13 flat-tensor all_gather: participants' tensors are
    CONCATENATED along dim 0 — :func:`all_gather` stacks them on a new
    leading dim; this flattens the first two dims to match torch."""
    g = all_gather(x, axis=axis, group=group)
    if g.ndim <= 1:
        return g  # scalar participants: stacked == concatenated
    return g.reshape((-1,) + tuple(g.shape[2:]))


def reduce_scatter_tensor(x, op: ReduceOp = ReduceOp.SUM, *, axis=None):
    """torch >= 1.13 flat-tensor reduce_scatter.

    Under hostring (real multi-process ranks) this is torch-exact: this
    rank's flat ``[world*n, ...]`` input returns its reduced ``[n, ...]``
    chunk. Under single-controller SPMD it reduces to
    :func:`reduce_scatter`'s facade semantics — the returned array holds
    EVERY chunk (reduced, sharded over the axis), this module's usual
    "SPMD produces the value everywhere" convention.
    """
    g = _group()
    if g.ring is not None:
        arr = np.asarray(x)
        w = g.ring.world_size
        if arr.shape[0] % w:
            raise ValueError(
                f"reduce_scatter_tensor input dim 0 ({arr.shape[0]}) must "
                f"divide by world_size {w}"
            )
        return jnp.asarray(
            g.ring.reduce_scatter(
                arr.reshape((w, arr.shape[0] // w) + arr.shape[1:]),
                op=op.value,
            )
        )
    return reduce_scatter(x, op, axis=axis)


def broadcast(x, src: int = 0, *, axis=None, group=None):
    """Replicate participant ``src``'s slice to everyone (shape x[0]).

    Under hostring: replicates rank ``src``'s local tensor (torch shape).
    With ``group``: ``src`` is a GLOBAL rank and must be a member."""
    g = _group()
    if group is not None:
        _no_axis_with_group(axis)
        if src not in group.ranks:
            raise ValueError(f"src {src} not in group ranks {group.ranks}")
        if g.ring is not None:
            _require_member(group, "broadcast")
            return jnp.asarray(
                group.ring.broadcast(
                    np.asarray(x), src=group.ranks.index(src)
                )
            )
        return _subgroup_rows(x, group)[group.ranks.index(src)]
    if g.ring is not None:
        return jnp.asarray(g.ring.broadcast(np.asarray(x), src=src))
    axes = _participant_axes(axis)
    x = jnp.asarray(x)
    size = _check_leading(x, axes, g.mesh)
    if not 0 <= src < size:
        raise ValueError(f"src {src} out of range for {size} participants")
    return jax.device_put(x[src], NamedSharding(g.mesh, P()))


def all_to_all(x, *, axis=None):
    """Each participant splits its row into per-peer chunks and exchanges.

    Input [participants, participants * chunk, ...]; output the same shape
    where ``out[p] = concat_j x[j][p-th chunk]`` — the facade translation
    of ``torch.distributed.all_to_all_single`` (the Ulysses/expert-parallel
    exchange). Rides the ICI as one XLA AllToAll.
    """
    g = _group()
    if g.ring is not None:
        return jnp.asarray(g.ring.all_to_all(np.asarray(x)))
    axes = _participant_axes(axis)
    x = jnp.asarray(x)
    size = _check_leading(x, axes, g.mesh)
    if x.ndim < 2 or x.shape[1] % size != 0:
        raise ValueError(
            f"all_to_all needs dim 1 divisible by participant count {size}, "
            f"got shape {x.shape}"
        )
    fn = _collective("all_to_all", ReduceOp.SUM, axes, g.mesh)
    return fn(jax.device_put(x, NamedSharding(g.mesh, P(axes))))


def permute(x, perm, *, axis=None):
    """Point-to-point block exchange: ``out[dst] = x[src]`` per (src, dst).

    The TPU-native replacement for NCCL send/recv pairs — a ``ppermute``
    whose transfers ride the ICI torus concurrently (neighbor exchanges,
    halo swaps, pipeline handoffs). Destinations no pair names receive
    zeros. For true host-side P2P under the multi-process backend, use
    ``HostRingGroup.send``/``recv``.
    """
    g = _group()
    if g.ring is not None:
        raise NotImplementedError(
            "permute is an SPMD collective; under the hostring backend use "
            "HostRingGroup.send/recv"
        )
    axes = _participant_axes(axis)
    x = jnp.asarray(x)
    size = _check_leading(x, axes, g.mesh)
    perm = tuple((int(s), int(d)) for s, d in perm)
    for s, d in perm:
        if not (0 <= s < size and 0 <= d < size):
            raise ValueError(f"perm pair ({s},{d}) out of range for {size}")
    fn = _collective("permute", perm, axes, g.mesh)
    return fn(jax.device_put(x, NamedSharding(g.mesh, P(axes))))


def gather(x, dst: int = 0, *, axis=None, group=None):
    """Gather participant slices to ``dst`` (torch.distributed.gather).

    Single-controller SPMD has no per-rank host to collect *to* — the
    controller addresses every shard — so this is ``all_gather`` with the
    torch call shape; ``dst`` is accepted for recipe-script parity.
    """
    del dst
    return all_gather(x, axis=axis, group=group)


def reduce(x, dst: int = 0, op: ReduceOp = ReduceOp.SUM, *, axis=None,
           group=None):
    """Reduce to ``dst`` (torch.distributed.reduce).

    In torch only rank ``dst``'s output is defined; under single-controller
    SPMD (and over the hostring, where the shm ring computes the full
    reduction anyway) producing the reduced value everywhere costs nothing
    extra, so this is ``all_reduce`` with the torch call shape.
    """
    del dst
    return all_reduce(x, op=op, axis=axis, group=group)


def monitored_barrier(timeout_s: Optional[float] = None) -> None:
    """torch.distributed.monitored_barrier: a barrier that fails loudly.

    Under the hostring backend the native barrier already enforces the
    group's init-time deadline and poisons the group with a timeout error
    when a rank never arrives — exactly monitored_barrier's job, so this
    is that barrier; a per-call ``timeout_s`` differing from the compiled
    group deadline (tighter OR looser) cannot be honored and is rejected
    rather than silently ignored. Under single-controller SPMD there are
    no peer processes to straggle.
    """
    g = _group()
    if timeout_s is not None and g.ring is not None and (
        timeout_s != g.ring.timeout_s
    ):
        raise NotImplementedError(
            f"per-call timeout {timeout_s}s differs from the compiled "
            f"group deadline ({g.ring.timeout_s}s), which cannot be "
            "overridden per call in either direction; pass timeout_s at "
            "init_process_group instead"
        )
    barrier()


def scatter(x, src: int = 0, *, axis=None):
    """Scatter ``src``'s per-participant slices (torch.distributed.scatter).

    Input [participants, ...] (the list rank ``src`` would pass in torch);
    participant p's slice is row p — returned sharded over ``axis`` so each
    device holds exactly its row.
    """
    g = _group()
    if g.ring is not None:
        return jnp.asarray(g.ring.scatter(np.asarray(x), src=src))
    axes = _participant_axes(axis)
    x = jnp.asarray(x)
    size = _check_leading(x, axes, g.mesh)
    if not 0 <= src < size:
        raise ValueError(f"src {src} out of range for {size} participants")
    return jax.device_put(x, NamedSharding(g.mesh, P(axes)))


def barrier(group=None) -> None:
    """Synchronize: run a whole-mesh psum and block on the result.

    With ``group``: only the member ranks synchronize (hostring); a
    single controller is trivially synchronized already."""
    g = _group()
    if group is not None:
        if g.ring is not None:
            _require_member(group, "barrier")
            group.ring.barrier()
        return
    if g.ring is not None:
        g.ring.barrier()
        return
    n = g.size
    x = jnp.ones((n,), jnp.int32)
    out = all_reduce(x.reshape(n, 1), ReduceOp.SUM)
    jax.block_until_ready(out)


# --------------------------------------------------------------------------
# Object collectives (torch.distributed.all_gather_object /
# broadcast_object_list). Objects live on HOSTS, so the participant set is
# the PROCESS world, not the device mesh: hostring ranks, pod controllers,
# or the single controller (for which these are identities — there is one
# process, so its object list is already "every process's objects").
# --------------------------------------------------------------------------


def _pickle_bytes(obj) -> np.ndarray:
    import pickle

    return np.frombuffer(
        pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL), dtype=np.uint8
    )


def _unpickle(buf: np.ndarray):
    import pickle

    return pickle.loads(buf.tobytes())


def _gather_padded(gather_fn, world: int, payload: np.ndarray) -> list:
    """Two-phase variable-size object gather over a fixed-size transport:
    gather lengths, max-pad payloads, gather, unpickle each row."""
    lens = np.asarray(gather_fn(np.array([len(payload)], np.int64)))
    lens = lens.reshape(world)
    buf = np.zeros(int(lens.max()), np.uint8)
    buf[: len(payload)] = payload
    rows = np.asarray(gather_fn(buf)).reshape(world, -1)
    return [_unpickle(rows[r, : int(lens[r])]) for r in range(world)]


def all_gather_object(obj) -> list:
    """Gather one picklable object per process; returns the rank-ordered list.

    Ranks may contribute different-sized (or different-typed) objects.
    """
    g = _group()
    if g.ring is not None:
        return _gather_padded(
            g.ring.all_gather, g.ring.world_size, _pickle_bytes(obj)
        )
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        return _gather_padded(
            multihost_utils.process_allgather,
            jax.process_count(),
            _pickle_bytes(obj),
        )
    return [obj]


def _process_world_size(g) -> int:
    if g.ring is not None:
        return g.ring.world_size
    return jax.process_count()


def scatter_object_list(objs: Optional[list], src: int = 0):
    """torch.distributed.scatter_object_list: process ``src`` supplies one
    object per process; each process receives its own. Non-src ranks may
    pass None. Single controller: returns ``objs[0]`` (a one-process
    world's scatter is the identity on its own slot).

    Failure mode (same as torch): the src-side length check below raises
    only on ``src`` — by then non-src ranks are already waiting in the
    broadcast, and they sit there until the group deadline poisons the
    group. A malformed src list is therefore an immediate error on src
    but a delayed group-timeout on its peers.
    """
    g = _group()
    world = _process_world_size(g)
    if not 0 <= src < world:
        raise ValueError(f"src {src} out of range for {world}-process world")
    rank = get_rank()  # ring rank under hostring, process index otherwise
    is_src = rank == src
    if is_src:
        if objs is None or len(objs) != world:
            raise ValueError(
                f"src must pass exactly {world} objects, got "
                f"{None if objs is None else len(objs)}"
            )
    if world == 1:
        return objs[0]
    # route through the object broadcast: src ships the whole list once
    # (object payloads are small control-plane data by contract; a
    # byte-exact per-rank scatter would save bandwidth, not semantics)
    return broadcast_object_list(
        objs if is_src else [None] * world, src=src
    )[rank]


def broadcast_object_list(objs: list, src: int = 0) -> list:
    """Replace every element with process ``src``'s list (torch semantics,
    but returned rather than mutated in place)."""
    g = _group()
    world = _process_world_size(g)
    if not 0 <= src < world:
        raise ValueError(
            f"src {src} out of range for {world}-process world"
        )
    # only src serializes (torch semantics): non-src ranks may hold
    # unpicklable placeholders and still participate
    if g.ring is not None:
        is_src = g.ring.rank == src
        payload = (
            _pickle_bytes(objs) if is_src else np.zeros(0, np.uint8)
        )
        n = int(
            np.asarray(
                g.ring.broadcast(np.array([len(payload)], np.int64), src=src)
            )[0]
        )
        buf = payload if is_src else np.zeros(n, np.uint8)
        return _unpickle(np.asarray(g.ring.broadcast(buf, src=src)))
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        # broadcast_one_to_all ships process 0's value; for src != 0 route
        # through an allgather (non-src contributes None, so only src's
        # payload is ever pickled) and pick the source's row
        is_src = jax.process_index() == src
        if src == 0:
            payload = (
                _pickle_bytes(objs) if is_src else np.zeros(0, np.uint8)
            )
            n = int(
                np.asarray(
                    multihost_utils.broadcast_one_to_all(
                        np.array([len(payload)], np.int64)
                    )
                )[0]
            )
            buf = np.zeros(n, np.uint8)
            if is_src:
                buf[:] = payload
            out = np.asarray(multihost_utils.broadcast_one_to_all(buf))
            return _unpickle(out)
        return all_gather_object(objs if is_src else None)[src]
    return list(objs)
