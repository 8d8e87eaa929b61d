#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process: loads, warms up every shape the cell uses (set-up),
measures for ``--seconds``, compares what the timed path produced with
the configuration's plain reference, prints the numbers compared beside
their limits and, as the last line of standard output, one JSON object
with ``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(``breakdown`` too under ``--trace 1``). ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics.

Without ``--rehearse-cpu`` anything but a TPU is an error before a model
is built. ``--rehearse-cpu`` runs the same code at the toy sizes of the
cell's ``rehearsal`` block on the CPU, kernels interpreted: it proves
control flow only, reports no metric and prints no result line.
"""

import time

_T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None,
                   help="length of the measured window "
                   "(default: BENCHMARK.json's run_seconds)")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="toy sizes on the CPU; prints no result line")
    return p.parse_args(argv)


def main(argv=None, *, overrides=None):
    args = parse_args(argv)
    from perfbench.harness import cells, device, result

    if not os.path.isdir(os.path.join(ROOT, "pytorch_distributed_tpu")):
        print("perfbench: the program (pytorch_distributed_tpu/) is not "
              "in this checkout; nothing was run", file=sys.stderr)
        return 2
    try:
        cell = cells.Cell(args.workload)
    except cells.CellError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    try:
        devices = device.require(cell.chips, args.rehearse_cpu)
    except device.NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    seconds = args.seconds
    if seconds is None:
        seconds = float(cell.benchmark["run_seconds"])
    run = result.Run(
        cell=cell, seed=args.seed, seconds=seconds, trace=bool(args.trace),
        rehearse=args.rehearse_cpu, devices=devices, t_process=_T_PROCESS,
        overrides=overrides or {},
    )
    outcome = cell.kind_module().run(run)
    return result.report(run, outcome)


if __name__ == "__main__":
    sys.exit(main())
