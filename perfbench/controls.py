#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (not a cell run).

    python3 perfbench/controls.py --workload <cell> --seeds 1,2,3 \\
        --what program,control,half_batch [--seconds 3]

For each seed and each ``what``: ``program`` makes a short run of the
cell in this process and prints the numbers its comparison read;
anything else is handed to the cell kind's ``control(run, what)``: the
plain reference put in the program's place, in the lower precision the
configuration names or with a fault planted. One JSON line per reading
on standard output, and in ``chiprun_out/controls/<cell>.jsonl``.

Give a serving cell ONE seed per call: a process that has built one
``ServeEngine`` at the cell's size does not get all of its device memory
back for the next (the sixth engine of one process failed to load its
decode program on the chip). A training cell takes a list.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--what", default="program,control")
    p.add_argument("--seconds", type=float, default=3.0)
    p.add_argument("--rehearse-cpu", action="store_true")
    args = p.parse_args(argv)
    from perfbench.harness import cells, device, result

    cell = cells.Cell(args.workload)
    devices = device.require(cell.chips, args.rehearse_cpu)
    out_dir = os.path.join(ROOT, "chiprun_out", "controls")
    os.makedirs(out_dir, exist_ok=True)
    kind = cell.kind_module()
    with open(os.path.join(out_dir, f"{cell.name}.jsonl"), "a") as log:
        for what in args.what.split(","):
            for seed in (int(s) for s in args.seeds.split(",")):
                t0 = time.perf_counter()
                run = result.Run(
                    cell=cell, seed=seed, seconds=args.seconds, trace=False,
                    rehearse=args.rehearse_cpu, devices=devices,
                    t_process=t0,
                )
                if what == "program":
                    outcome = kind.run(run)
                    readings = {c.name: c.value for c in outcome.checks}
                    readings.update(outcome.end_to_end)
                    readings["failed"] = outcome.failed
                else:
                    readings = kind.control(run, what)
                rec = {"cell": cell.name, "what": what, "seed": seed,
                       "platform": devices[0].platform,
                       "seconds": round(time.perf_counter() - t0, 1),
                       **readings}
                line = json.dumps(rec)
                print(line, flush=True)
                log.write(line + "\n")
                log.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
