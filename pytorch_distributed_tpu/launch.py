"""Launchers: the torchrun / ``mp.spawn`` equivalents.

The reference launches one process per GPU via ``torchrun`` (PyTorch's
elastic agent) or ``torch.multiprocessing.spawn`` (BASELINE.json:5,
SURVEY.md §2). The TPU-native execution model is single-controller SPMD —
ONE process drives every local chip — so the launcher's three jobs map to:

* ``spawn(fn, nprocs)``         — mp.spawn texture for the multi-process
  CPU path (workers join the native hostring backend; the gloo recipe).
* ``ElasticAgent`` / CLI        — torchrun texture: supervise worker
  processes, tear the group down on any failure, re-rendezvous and retry
  up to ``max_restarts`` (failure detection + elastic recovery, SURVEY §5).
* ``init_multihost()``          — the pod story: on a TPU pod slice each
  *host* runs one controller process; ``jax.distributed.initialize`` is
  the rendezvous (the NCCL TCP-store equivalent). Accepts both JAX-style
  and torchrun-style (MASTER_ADDR/MASTER_PORT/RANK/WORLD_SIZE) env.
* ``ElasticWorldLauncher``      — supervisor for the IN-PROCESS elastic
  path (``train/elastic_world.py``): starts a genesis world and can add
  joiners mid-run; unlike ``ElasticAgent`` it never restarts anything —
  membership changes are handled by the workers re-meshing in place.

CLI: ``python -m pytorch_distributed_tpu.run --nproc-per-node 4 script.py``.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import signal
import subprocess
import sys
import time
import uuid
from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence


def _worker_env(
    rank: int,
    world_size: int,
    group_name: str,
    *,
    node_rank: int = 0,
    nproc_per_node: Optional[int] = None,
    platform: str = "cpu",
    base: Optional[dict] = None,
) -> dict:
    """Env block for one worker, torchrun-shaped."""
    nproc = nproc_per_node or world_size
    env = dict(base if base is not None else os.environ)
    env.update(
        RANK=str(rank),
        WORLD_SIZE=str(world_size),
        LOCAL_RANK=str(rank % nproc),
        LOCAL_WORLD_SIZE=str(nproc),
        GROUP_RANK=str(node_rank),
        MASTER_ADDR=env.get("MASTER_ADDR", "127.0.0.1"),
        MASTER_PORT=env.get("MASTER_PORT", "29500"),
        PTD_GROUP_NAME=group_name,
        # Workers must not fight over the (single) local TPU; the chip
        # belongs to the single-controller path. Opt in via platform="tpu"
        # only when each worker has its own slice (multi-host).
        JAX_PLATFORMS=platform,
    )
    if platform == "tpu":
        # tpu workers are one-controller-per-HOST: init_process_group must
        # rendezvous via jax.distributed (RANK = host index), never join
        # the host-local shm ring with the global world size.
        env["PTD_MULTIHOST"] = "1"
    return env


def _spawn_target(fn, rank, world_size, group_name, platform, args):
    # The child inherited the parent env at interpreter start; overlay the
    # per-rank identity before user code runs.
    os.environ.update(
        _worker_env(rank, world_size, group_name, platform=platform, base={})
    )
    fn(rank, *args)


def spawn(
    fn: Callable,
    args: Sequence = (),
    nprocs: int = 1,
    *,
    join: bool = True,
    platform: str = "cpu",
    timeout_s: float = 600.0,
):
    """``torch.multiprocessing.spawn`` equivalent.

    Runs ``fn(rank, *args)`` in ``nprocs`` fresh processes with
    torchrun-shaped env (RANK/WORLD_SIZE/...) so ``init_process_group``
    inside ``fn`` joins the multi-process hostring backend. ``fn`` must be
    picklable (module-level). Returns the list of processes if
    ``join=False``.
    """
    ctx = mp.get_context("spawn")
    group_name = f"ptd_spawn_{uuid.uuid4().hex[:8]}"
    old_env = {"JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS")}
    # spawn children inherit the parent env at interpreter start — the
    # chip belongs to ONE process (the single-controller parent), so keep
    # the children off it even before fn runs.
    os.environ["JAX_PLATFORMS"] = platform
    procs = []
    try:
        procs = [
            ctx.Process(
                target=_spawn_target,
                args=(fn, r, nprocs, group_name, platform, tuple(args)),
            )
            for r in range(nprocs)
        ]
        for p in procs:
            p.start()
    except BaseException:
        # partial start: reap the workers already running — they'd block
        # in the rendezvous waiting for ranks that will never come
        for p in procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=10)
        from pytorch_distributed_tpu.runtime.hostring import unlink_segment

        unlink_segment(group_name)
        raise
    finally:
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    if not join:
        return procs
    deadline = time.monotonic() + timeout_s
    try:
        for p in procs:
            p.join(timeout=max(0.1, deadline - time.monotonic()))
        bad = [
            (p.pid, p.exitcode) for p in procs if p.exitcode not in (0, None)
        ]
        hung = [p.pid for p in procs if p.exitcode is None]
        if bad or hung:
            raise RuntimeError(
                f"spawn workers failed: nonzero={bad} hung={hung}"
            )
    finally:
        dirty = False
        for p in procs:
            if p.is_alive():
                p.terminate()
                dirty = True
            elif p.exitcode != 0:
                dirty = True
        if dirty:
            # killed/crashed workers never reach hr_finalize
            from pytorch_distributed_tpu.runtime.hostring import unlink_segment

            unlink_segment(group_name)
    return None


@dataclass
class ElasticAgent:
    """torchrun-equivalent supervisor for command-line workers.

    Launches ``nproc_per_node`` copies of ``cmd`` with torchrun-shaped env,
    watches them, and on any worker failure tears the whole group down and
    re-rendezvouses (fresh shm group name) up to ``max_restarts`` times —
    the reference's elastic-agent restart policy (SURVEY.md §5: failure
    detection / elastic recovery).
    """

    cmd: Sequence[str]
    nproc_per_node: int
    max_restarts: int = 3
    node_rank: int = 0
    nnodes: int = 1
    platform: str = "cpu"
    poll_s: float = 0.2
    extra_env: dict = field(default_factory=dict)

    def _launch_once(self, attempt: int) -> int:
        world = self.nproc_per_node * self.nnodes
        group_name = f"ptd_run_{uuid.uuid4().hex[:8]}_a{attempt}"
        procs = []
        for local in range(self.nproc_per_node):
            rank = self.node_rank * self.nproc_per_node + local
            env = _worker_env(
                rank, world, group_name,
                node_rank=self.node_rank,
                nproc_per_node=self.nproc_per_node,
                platform=self.platform,
            )
            env.update({k: str(v) for k, v in self.extra_env.items()})
            env["TORCHELASTIC_RESTART_COUNT"] = str(attempt)
            procs.append(subprocess.Popen(list(self.cmd), env=env))
        try:
            while True:
                codes = [p.poll() for p in procs]
                if all(c == 0 for c in codes):
                    return 0
                failed = [
                    (p.pid, c) for p, c in zip(procs, codes)
                    if c is not None and c != 0
                ]
                if failed:
                    print(
                        f"[ptd.run] worker failure {failed}; "
                        "tearing down group",
                        file=sys.stderr,
                    )
                    return failed[0][1]
                time.sleep(self.poll_s)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            t0 = time.monotonic()
            for p in procs:
                while p.poll() is None and time.monotonic() - t0 < 10:
                    time.sleep(0.1)
                if p.poll() is None:
                    p.kill()
                    p.wait()
            # killed workers never reach hr_finalize; reap their segment
            from pytorch_distributed_tpu.runtime.hostring import unlink_segment

            unlink_segment(group_name)

    def run(self) -> int:
        if self.nnodes > 1 and self.platform == "cpu":
            raise ValueError(
                "nnodes > 1 requires --platform tpu (multi-host pods "
                "rendezvous via init_multihost); the cpu/hostring backend "
                "is host-local shared memory and cannot span nodes"
            )
        for attempt in range(self.max_restarts + 1):
            code = self._launch_once(attempt)
            if code == 0:
                return 0
            if attempt < self.max_restarts:
                print(
                    f"[ptd.run] restart {attempt + 1}/{self.max_restarts}",
                    file=sys.stderr,
                )
        return code


@dataclass
class ElasticWorldLauncher:
    """Launch / supervise ``train/elastic_world.py`` worker processes.

    The torchrun-agent counterpart for the IN-PROCESS elastic path: it
    starts the genesis world and can ``add_worker`` (the grow drill) —
    but unlike :class:`ElasticAgent` it never tears the group down on a
    failure; membership changes are the workers' own business. One
    launcher = one rendezvous dir. Shared by ``scripts/chaos_drill.py
    --drill resize``, bench.py's ``elastic`` phase, and the tests.
    """

    rendezvous_dir: str
    worker_args: Sequence[str] = ()  # engine CLI flags, minus identity
    python: Optional[str] = None

    def __post_init__(self):
        os.makedirs(self.rendezvous_dir, exist_ok=True)
        self.procs: dict = {}

    def _cmd(self, worker_id: str, extra: Sequence[str]) -> list:
        return [
            self.python or sys.executable, "-m",
            "pytorch_distributed_tpu.train.elastic_world",
            "--rendezvous-dir", self.rendezvous_dir,
            "--worker-id", worker_id,
            *self.worker_args, *extra,
        ]

    def start_world(self, worker_ids: Sequence[str],
                    env_overrides: Optional[dict] = None) -> None:
        """Genesis: every worker gets ``--expected-world len(ids)``.

        ``env_overrides`` maps worker_id -> extra env (the drill arms
        one worker's ``PTD_FAULTS`` here to pick the deterministic
        victim)."""
        for wid in worker_ids:
            self.launch_worker(
                wid, extra=("--expected-world", str(len(worker_ids))),
                env=(env_overrides or {}).get(wid),
            )

    def add_worker(self, worker_id: str,
                   env: Optional[dict] = None) -> None:
        """The grow path: a fresh process joins the live world."""
        self.launch_worker(worker_id, extra=("--join",), env=env)

    def launch_worker(self, worker_id: str, *, extra: Sequence[str] = (),
                      env: Optional[dict] = None) -> None:
        worker_env = dict(os.environ)
        # workers never touch the (single, shared) TPU
        worker_env["JAX_PLATFORMS"] = "cpu"
        worker_env.pop("XLA_FLAGS", None)
        # the -m target must resolve regardless of the caller's cwd
        repo_root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        prev = worker_env.get("PYTHONPATH")
        worker_env["PYTHONPATH"] = (
            repo_root if not prev else repo_root + os.pathsep + prev
        )
        worker_env.update(env or {})
        self.procs[worker_id] = subprocess.Popen(
            self._cmd(worker_id, extra), env=worker_env,
            stdout=sys.stderr, stderr=subprocess.STDOUT,
        )

    def wait(self, timeout_s: float = 180.0) -> dict:
        """Join every worker; returns worker_id -> exit code."""
        deadline = time.monotonic() + timeout_s
        codes = {}
        try:
            for wid, p in self.procs.items():
                left = max(0.1, deadline - time.monotonic())
                try:
                    codes[wid] = p.wait(timeout=left)
                except subprocess.TimeoutExpired:
                    codes[wid] = None
        finally:
            for p in self.procs.values():
                if p.poll() is None:
                    p.kill()
                    p.wait()
        return codes

    def results(self) -> dict:
        """worker_id -> parsed result-<id>.json (absent workers omitted)."""
        import json

        out = {}
        for wid in self.procs:
            path = os.path.join(
                self.rendezvous_dir, f"result-{wid}.json"
            )
            try:
                with open(path) as f:
                    out[wid] = json.load(f)
            except (OSError, ValueError):
                pass
        return out


def init_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host (pod) rendezvous: ``jax.distributed.initialize`` with
    torchrun-style env fallbacks.

    On a TPU pod each host runs ONE controller process; after this call
    ``jax.devices()`` spans the whole pod and every mesh built on top of it
    shards over ICI/DCN. Resolution order per field: explicit arg →
    JAX-style env (COORDINATOR_ADDRESS/NUM_PROCESSES/PROCESS_ID) →
    torchrun-style env (MASTER_ADDR:MASTER_PORT / WORLD_SIZE / RANK) →
    jax autodetection (GKE/Cloud TPU metadata).
    """
    import jax

    def pick(explicit, *env_keys, cast=str):
        if explicit is not None:
            return explicit
        for k in env_keys:
            if os.environ.get(k):
                return cast(os.environ[k])
        return None

    coordinator_address = pick(
        coordinator_address, "COORDINATOR_ADDRESS"
    )
    if coordinator_address is None and os.environ.get("MASTER_ADDR"):
        coordinator_address = (
            f"{os.environ['MASTER_ADDR']}:"
            f"{os.environ.get('MASTER_PORT', '29500')}"
        )
    num_processes = pick(num_processes, "NUM_PROCESSES", "WORLD_SIZE", cast=int)
    process_id = pick(process_id, "PROCESS_ID", "RANK", cast=int)
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
