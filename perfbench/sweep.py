#!/usr/bin/env python3
"""The knee of an open-loop cell, found once by a sweep on the chip.

    python3 perfbench/sweep.py --workload <cell> --rates 20,40,60 --seconds 10

Runs the cell's mix at each offered rate (everything else as the cell's
files say) and prints one JSON line per rate: what was due and what
completed inside the window, the tails, and how late the generator ran.
The knee is the highest rate the system sustains: the last before the
tails leave the plateau and requests due in the window stop completing
in it. The cell's traffic file then states 0.8 x that number. Not a cell
run: it prints no result line.
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
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--rehearse-cpu", action="store_true")
    args = p.parse_args(argv)
    from perfbench.harness import cells, device, result

    cell = cells.Cell(args.workload)
    devices = device.require(cell.chips, args.rehearse_cpu)
    kind = cell.kind_module()
    out_dir = os.path.join(ROOT, "chiprun_out", "sweeps")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{cell.name}.jsonl"), "a") as log:
        for rate in (float(r) for r in args.rates.split(",")):
            arrivals = dict(cell.traffic["arrivals"], rate_per_s=rate)
            run = result.Run(
                cell=cell, seed=args.seed, seconds=args.seconds,
                trace=False, rehearse=args.rehearse_cpu, devices=devices,
                t_process=time.perf_counter(),
                overrides={"traffic": {"arrivals": arrivals,
                                       "drain_limit_s": 20.0}},
            )
            o = kind.run(run)
            t0, t1 = o.context["window"]
            reqs = [r for r in o.context["requests"] if t0 <= r["due"] < t1]
            done_in = sum(
                1 for r in reqs if r["completed"] and r["stamps"][-1] < t1
            )
            rec = {
                "cell": cell.name, "rate_per_s": rate,
                "due_in_window": len(reqs), "completed_in_window": done_in,
                "failed": o.failed, **o.end_to_end,
                "correct": all(c.ok for c in o.checks),
            }
            for note in o.notes:
                print(note, flush=True)
            line = json.dumps(rec)
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
