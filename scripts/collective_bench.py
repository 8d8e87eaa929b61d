"""Collective microbenchmarks over any mesh — the allreduce-step-time tool.

The reference's secondary north-star metric is "DDP allreduce step time"
(BASELINE.json:2). On a single chip that collective is compiler-eliminated
(bench.py measures DP-step *overhead* instead); the moment a multi-chip
mesh exists — ICI slice or multi-host pod — this script measures the real
thing: per-collective latency and achieved algorithmic bandwidth for the
facade's all_reduce / all_gather / reduce_scatter / permute at gradient
sizes, over whichever mesh axis you give it.

Bus-bandwidth accounting follows the NCCL-tests convention so numbers are
comparable to the reference's GPU rigs:

    allreduce      moves 2(n-1)/n * bytes   per participant
    allgather      moves   (n-1)/n * bytes
    reduce_scatter moves   (n-1)/n * bytes
    permute        moves             bytes  (one hop on the ring)

On the virtual CPU mesh (XLA_FLAGS=--xla_force_host_platform_device_count=N)
the "collectives" are shared-memory copies — the run is a harness smoke,
not a measurement; the banner says which you got.

``--metrics-path`` writes every (op, size, world) measurement through
the MetricsWriter JSONL protocol (``split="comm_bench"``,
``event="collective"``) so cost-model fits and bench history can
consume past runs instead of re-parsing stdout prose. ``--fit PATH``
calibrates the α–β comms cost model (runtime/costmodel.py) from this
run's sweep and writes the ``costmodel.json`` artifact the
auto-parallel planner (ROADMAP item 4) consumes; the fit summary
prints each op's α/β/R² and the worst predicted-vs-measured ratio over
the sweep (the "within 2x" self-check).

``--transport tcp`` (or ``shm``) bypasses the jax facade entirely: it
spawns ``--world`` jax-free worker processes running the REAL transport
(runtime/transport.py) under :class:`HostRingGroup` and sweeps the host
collectives — all_reduce, all_reduce_q8, all_gather, reduce_scatter,
broadcast — so ``--fit`` writes a model whose ``transport`` label is the
thing actually measured. One per-transport model file per transport:
``CostModel.load(expected_transport=...)`` refuses the wrong one.

Run:
    python scripts/collective_bench.py --sizes 4 32 128
    python scripts/collective_bench.py --axis dp --iters 50
    python scripts/collective_bench.py --sizes 1 4 16 64 \
        --metrics-path runs/comm.jsonl --fit runs/costmodel.json
    python scripts/collective_bench.py --transport tcp --world 2 \
        --sizes 1 4 16 --fit runs/costmodel_tcp.json
"""

import argparse
import multiprocessing
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _timed(fn, x, iters, warmup=3):
    import jax

    y = fn(x)
    for _ in range(warmup):
        y = fn(y)
    jax.block_until_ready(y)
    t0 = time.perf_counter()
    for _ in range(iters):
        y = fn(y)
    jax.block_until_ready(y)
    return (time.perf_counter() - t0) / iters


def _transport_worker(rank, world, name, q, kind, addr, sizes_mb, iters,
                      slot_bytes):
    """One spawn-context rank of the ``--transport`` sweep (jax-free)."""
    import numpy as np

    from pytorch_distributed_tpu.runtime.hostring import HostRingGroup
    from pytorch_distributed_tpu.runtime.transport import TcpTransport

    try:
        tp = None
        if kind == "tcp":
            tp = TcpTransport(name, rank, world, addr,
                              slot_bytes=slot_bytes)
        ring = HostRingGroup(name, rank, world, slot_bytes=slot_bytes,
                             transport=tp)
        records = []
        for mb in sizes_mb:
            # elems divisible by world (reduce_scatter rows) AND by 256
            # (q8 block grid) so every op runs the same logical payload
            elems = max(int(mb * 1e6 / 4) // (world * 256), 1) * world * 256
            payload = elems * 4
            per = elems // world
            cases = {
                "all_reduce": (
                    np.ones(elems, np.float32),
                    lambda a: ring.all_reduce(a, inplace=True),
                ),
                "all_reduce_q8": (
                    np.ones(elems, np.float32),
                    lambda a: ring.all_reduce_q8(a, inplace=True),
                ),
                "all_gather": (
                    np.ones(per, np.float32),
                    lambda a: ring.all_gather(a),
                ),
                "reduce_scatter": (
                    np.ones((world, per), np.float32),
                    lambda a: ring.reduce_scatter(a),
                ),
                "broadcast": (
                    np.ones(elems, np.float32),
                    lambda a: ring.broadcast(a, 0, inplace=True),
                ),
            }
            if elems < 256 * world:
                del cases["all_reduce_q8"]  # below the q8 segment floor
            for op, (x, fn) in cases.items():
                for _ in range(2):
                    fn(x)
                ring.barrier()
                t0 = time.perf_counter()
                for _ in range(iters):
                    fn(x)
                dt = (time.perf_counter() - t0) / iters
                if rank == 0:
                    records.append({
                        "op": op, "payload_bytes": payload,
                        "seconds": dt, "world": world, "iters": iters,
                    })
        ring.close()
        q.put((rank, "ok", records))
    except Exception as e:  # surfaced by the parent
        q.put((rank, "error", f"{type(e).__name__}: {e}"))


def _transport_sweep(args):
    """Spawn a world of transport workers; returns rank 0's records."""
    from pytorch_distributed_tpu.runtime.hostring import unlink_segment

    ctx = multiprocessing.get_context("spawn")
    q = ctx.Queue()
    name = f"cbench_{os.getpid()}"
    addr = "127.0.0.1:0"
    if args.transport == "tcp":
        # pick a concrete free port up front: every rank needs the same
        # dial address before rank 0's listener exists
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        addr = f"127.0.0.1:{s.getsockname()[1]}"
        s.close()
    procs = [
        ctx.Process(
            target=_transport_worker,
            args=(r, args.world, name, q, args.transport, addr,
                  args.sizes, args.iters, int(args.slot_mb * 1e6)),
        )
        for r in range(args.world)
    ]
    for p in procs:
        p.start()
    results = {}
    try:
        for _ in range(args.world):
            r, status, payload = q.get(timeout=600)
            if status != "ok":
                raise RuntimeError(f"rank {r} failed: {payload}")
            results[r] = payload
    finally:
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()
        if args.transport == "shm":
            unlink_segment(name)
    return results.get(0, [])


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--sizes", type=float, nargs="+", default=[4.0, 32.0],
                   help="payload sizes in MB (f32 elements)")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--axis", default=None,
                   help="mesh axis to run over (default: the whole mesh)")
    p.add_argument("--dp", type=int, default=-1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--fsdp", type=int, default=1)
    p.add_argument("--metrics-path", default=None,
                   help="append per-(op, size, world) records as "
                   "MetricsWriter JSONL (split=comm_bench)")
    p.add_argument("--fit", default=None, metavar="COSTMODEL_JSON",
                   help="fit the α–β comms cost model from this sweep "
                   "and write it here")
    p.add_argument("--transport", default="auto",
                   choices=("auto", "shm", "tcp"),
                   help="auto = the jax facade sweep below; shm/tcp = "
                   "spawn a jax-free HostRingGroup worker ring on that "
                   "transport and sweep the host collectives")
    p.add_argument("--world", type=int, default=2,
                   help="worker count for --transport shm/tcp sweeps")
    p.add_argument("--slot-mb", type=float, default=4.0,
                   help="transport slot size (MB) for --transport sweeps")
    args = p.parse_args(argv)

    if args.transport != "auto":
        from pytorch_distributed_tpu.runtime.hostring import (
            algo_wire_bytes,
        )

        if args.world < 2:
            print("# --transport sweeps need --world >= 2",
                  file=sys.stderr)
            return 1
        transport = args.transport
        print(f"# transport={transport} world={args.world} "
              f"(host collectives over runtime/transport.py; "
              f"loopback physics on one box)", flush=True)
        records = []
        for r in _transport_sweep(args):
            wire = algo_wire_bytes(r["op"], r["payload_bytes"],
                                   r["world"])
            rec = {**r, "wire_bytes": wire,
                   "gb_per_s": wire / r["seconds"] / 1e9,
                   "transport": transport}
            records.append(rec)
            print(
                f"{rec['op']:15s} {rec['payload_bytes'] / 1e6:8.1f}MB "
                f"{rec['seconds'] * 1e3:8.3f}ms  "
                f"{rec['gb_per_s']:7.2f} GB/s busbw",
                flush=True,
            )
        return _write_outputs(args, records, transport)

    import jax.numpy as jnp  # noqa: F401 — facade path only

    import pytorch_distributed_tpu as ptd
    from pytorch_distributed_tpu.runtime.distributed import ReduceOp
    from pytorch_distributed_tpu.runtime.mesh import (
        MeshSpec,
        mesh_axis_size,
    )

    ptd.enable_compilation_cache()
    if not ptd.is_initialized():
        # guarded: embedding callers (tests, notebooks) keep their mesh
        ptd.init_process_group(
            mesh_spec=MeshSpec(dp=args.dp, tp=args.tp, fsdp=args.fsdp)
        )
    plat = ptd.platform()
    # participant count follows the requested axis, not the whole mesh —
    # the leading dim of every facade collective input must match it
    parts = (
        mesh_axis_size(args.axis) if args.axis else ptd.get_world_size()
    )
    print(f"# platform={plat} participants={parts} "
          f"axis={args.axis or '<all>'} "
          f"({'REAL collectives' if plat == 'tpu' and parts > 1 else 'smoke only: single device or shared-memory mesh'})",
          flush=True)
    if parts == 1:
        print("# 1 participant: collectives are identity; nothing to measure")
        return
    # transport label for records/model: the facade's XLA collectives on
    # this platform, or the native shm ring under a one-proc-per-rank
    # launch — a model fitted on one must not silently price the other
    from pytorch_distributed_tpu.runtime.distributed import (
        multiprocess_ring,
    )

    transport = (
        "hostring" if multiprocess_ring() is not None else f"spmd:{plat}"
    )
    records = []

    kw = {"axis": args.axis} if args.axis else {}
    colls = {
        # facade semantics: leading dim = participants. Every fn is
        # shape-preserving so the timed loop can chain output -> input
        # (one compile, real data dependencies between iterations).
        "all_reduce": (
            lambda x: jnp.broadcast_to(
                ptd.all_reduce(x, op=ReduceOp.AVG, **kw), x.shape
            ),
            lambda n, b: 2 * (n - 1) / n * b,
        ),
        "reduce_scatter": (
            lambda x: jnp.broadcast_to(
                ptd.reduce_scatter(x, op=ReduceOp.SUM, **kw), x.shape
            ),
            lambda n, b: (n - 1) / n * b,
        ),
        "all_gather": (
            # [parts, per] in -> [parts, per] replicated out: each
            # participant contributes its row
            lambda x: ptd.all_gather(x, **kw),
            lambda n, b: (n - 1) / n * b,
        ),
        "permute": (
            lambda x: ptd.permute(
                x, [(i, (i + 1) % parts) for i in range(parts)], **kw
            ),
            lambda n, b: b,
        ),
    }
    for mb in args.sizes:
        n_elem = int(mb * 1e6 / 4)
        # per-participant rows sized divisibly by parts so reduce_scatter's
        # tiled scatter dimension splits evenly
        per = max(n_elem // parts // parts, 1) * parts
        x = jnp.ones((parts, per), jnp.float32)
        payload = per * parts * 4
        for name, (fn, moved) in colls.items():
            try:
                dt = _timed(fn, x, args.iters)
                bw = moved(parts, payload) / dt / 1e9
                print(
                    f"{name:15s} {payload / 1e6:8.1f}MB "
                    f"{dt * 1e3:8.3f}ms  {bw:7.2f} GB/s busbw",
                    flush=True,
                )
                records.append({
                    "op": name,
                    "payload_bytes": payload,
                    "wire_bytes": int(moved(parts, payload)),
                    "seconds": dt,
                    "gb_per_s": bw,
                    "world": parts,
                    "transport": transport,
                    "iters": args.iters,
                })
            except Exception as e:  # keep later collectives running
                print(f"{name:15s} {payload / 1e6:8.1f}MB FAILED: "
                      f"{type(e).__name__}: {e}", flush=True)

    return _write_outputs(args, records, transport)


def _write_outputs(args, records, transport):
    """Shared tail of both sweep paths: JSONL records + the α–β fit."""
    if args.metrics_path:
        from pytorch_distributed_tpu.train.metrics import MetricsWriter

        with MetricsWriter(args.metrics_path) as w:
            for i, r in enumerate(records):
                w.write(i, {"event": "collective", **r},
                        split="comm_bench")
        print(f"# {len(records)} records -> {args.metrics_path}",
              flush=True)

    if args.fit:
        from pytorch_distributed_tpu.runtime import costmodel

        model = costmodel.fit(records, transport)
        if not model.fits:
            print("# --fit: no fittable measurements (all failed or "
                  "1 participant)", file=sys.stderr)
            return 1
        path = model.save(args.fit)
        worst = costmodel.validate(model, records)
        print(f"# cost model ({transport}) -> {path}", flush=True)
        for (op, world), f in sorted(model.fits.items()):
            print(
                f"# fit {op:15s} world={world} "
                f"alpha={f.alpha_s * 1e6:9.1f}us "
                f"beta={f.beta_s_per_byte * 1e9:8.4f}ns/B "
                f"({f.bandwidth_gb_s:6.2f} GB/s) r2={f.r2:.3f} "
                f"n={f.n_samples} worst_ratio={worst.get(op, 0.0):.2f}x",
                flush=True,
            )
        bad = {op: r for op, r in worst.items() if r > 2.0}
        if bad:
            print(f"# WARNING: predictions off by >2x on the calibration "
                  f"sweep itself: {bad} — more sizes or more iters",
                  file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
