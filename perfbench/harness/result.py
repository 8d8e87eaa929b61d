"""A run's arguments, what a cell's kind hands back, and the contract's
last line."""

import dataclasses
import json
import os
import statistics
import sys
from typing import Any, Dict, List, Optional

from perfbench.harness import device as device_mod
from perfbench.harness import trace as trace_mod


@dataclasses.dataclass
class Run:
    cell: Any
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    devices: list
    t_process: float
    overrides: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def setting(self, block):
        """``cell.spec[block]``, with the rehearsal's toy sizes and a
        test's overrides laid over it."""
        out = dict(self.cell.spec.get(block, {}))
        if self.rehearse:
            out.update(self.cell.spec.get("rehearsal", {}).get(block, {}))
        out.update(self.overrides.get(block, {}))
        return out

    def traffic(self):
        out = dict(self.cell.traffic)
        if self.rehearse:
            out.update(self.cell.traffic.get("rehearsal", {}))
        out.update(self.overrides.get("traffic", {}))
        return out

    def config(self):
        """The configuration as it is run: the file, or under
        ``--rehearse-cpu`` the file with the toy sizes of its cell."""
        out = dict(self.cell.config)
        if self.rehearse:
            out.update(self.cell.spec.get("rehearsal", {}).get("config", {}))
        out.update(self.overrides.get("config", {}))
        return out

    def policy(self):
        """The program's dtype ``Policy`` the configuration states."""
        import jax.numpy as jnp

        from pytorch_distributed_tpu.runtime.precision import Policy

        prec = self.config()["precision"]
        return Policy(
            param_dtype=jnp.dtype(prec["param_dtype"]),
            compute_dtype=jnp.dtype(prec["compute_dtype"]),
            output_dtype=jnp.dtype(prec["output_dtype"]),
        )

    def out_dir(self):
        """Scratch inside the checkout (gitignored): profiler traces."""
        path = os.path.join(self.cell.root, "perfbench_out", self.cell.name)
        os.makedirs(path, exist_ok=True)
        return path


@dataclasses.dataclass
class Check:
    """One number compared, beside its limit; it passes at or under it."""

    name: str
    value: Optional[float]
    limit: float

    @property
    def ok(self):
        return self.value is not None and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: List[Check]
    memory_peak_bytes: int
    context: Dict[str, Any]  # what the per-layer readers read
    notes: List[str] = dataclasses.field(default_factory=list)


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation; None of
    nothing."""
    if not values:
        return None
    v = sorted(values)
    k = (len(v) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def median(values):
    return statistics.median(values) if values else None


def report(run, outcome):
    """Print the comparison, then the contract's line; returns the exit
    code."""
    cell = run.cell
    correct = bool(outcome.checks) and all(c.ok for c in outcome.checks)
    units = {
        m["name"]: m["unit"]
        for m in cell.benchmark["end_to_end"] + cell.benchmark["per_layer"]
    }
    dev = device_mod.describe(run.devices, cell.chips)
    dev["memory_peak_bytes"] = outcome.memory_peak_bytes
    line = {"correct": correct, "attempted": outcome.attempted,
            "failed": outcome.failed}
    ctx = outcome.context
    if not run.trace:
        values = {
            m["name"]: outcome.end_to_end.get(m["name"])
            for m in cell.end_to_end()
        }
    else:
        values = {}
        for m in cell.per_layer():
            reader = cell.metric_reader(m["name"])
            try:
                values[m["name"]] = reader.read(ctx)
            except KeyError as e:
                # the CPU has no entry in the table of peaks: in the
                # rehearsal that reader is silent; on a chip it is a fault
                if not run.rehearse:
                    raise
                print(f"rehearsal: {m['name']} not read ({e})", flush=True)
        tr = ctx.get("trace") or {}
        dev["busy_s"] = tr.get("busy_s")
        dev["window_s"] = tr.get("window_s")
    line["metrics"] = {
        k: {"value": v, "unit": units[k]}
        for k, v in values.items() if v is not None
    }
    line["device"] = dev
    if run.trace:
        tr = ctx.get("trace") or {}
        line["breakdown"] = {
            "device_ops": trace_mod.top(tr.get("ops", {})),
            "idle_gaps": trace_mod.top(tr.get("idle_gaps", {})),
        }
    line["checks"] = {
        c.name: {"value": c.value, "limit": c.limit} for c in outcome.checks
    }
    for note in outcome.notes:
        print(note, flush=True)
    sys.stdout.flush()
    for c in outcome.checks:
        print(f"check {c.name}: value {c.value} limit {c.limit} "
              f"{'ok' if c.ok else 'NOT OK'}", file=sys.stderr)
    print(f"correct={correct}", file=sys.stderr, flush=True)
    if run.rehearse:
        print(f"rehearsal on {dev['platform']}: correct={correct}; the "
              f"readers returned {sorted(line['metrics'])}; no result "
              "line (a CPU run measures nothing)", flush=True)
        return 0 if correct else 1
    missing = [k for k, v in values.items() if v is None and not run.trace]
    if missing:
        print(f"perfbench: no value for {missing}", file=sys.stderr)
        return 4
    print(json.dumps(line), flush=True)
    return 0
