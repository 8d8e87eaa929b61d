"""From a profiler trace to busy and idle time, kernel time by name,
and the longest idle gaps by what the host was doing.

The reduction works on plain tuples ``(plane, line, name, start_ns,
dur_ns)`` so that it can be checked on a small recorded trace
(``tests/benchmark``); :func:`read_xplane` is the thin reader that
produces them from the ``.xplane.pb`` the JAX profiler writes.
"""

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINES = ("XLA Ops",)  # the per-operation line of a TPU device plane
MODULES_LINE = "XLA Modules"  # one event per execution of a whole program
MARK = "perfbench.mark"


def start(logdir):
    """Start the JAX profiler without the python tracer (it records
    every python call, which slows the host it is measuring)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(logdir, profiler_options=options)


def stop():
    import jax

    jax.profiler.stop_trace()


def find_xplane(logdir):
    paths = sorted(glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def read_xplane(path, planes=None):
    """Every event of the trace as ``(plane, line, name, start_ns,
    dur_ns)``; ``planes`` keeps planes whose name starts with one of the
    given prefixes."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if planes and not any(plane.name.startswith(p) for p in planes):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((
                    plane.name, line.name, ev.name,
                    float(ev.start_ns), float(ev.duration_ns),
                ))
    return out


def summarize_lines(events):
    """``{(plane, line): (count, first few names)}`` — what to look at
    by hand before trusting the reduction on a new device."""
    out = {}
    for plane, line, name, _, _ in events:
        n, names = out.setdefault((plane, line), [0, []])
        out[(plane, line)][0] = n + 1
        if len(names) < 4 and name not in names:
            names.append(name)
    return {k: tuple(v) for k, v in out.items()}


def union_seconds(intervals):
    """Length of the union of ``(start_ns, end_ns)`` intervals, and the
    merged intervals themselves."""
    merged = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged) / 1e9, merged


CONTAINERS = ("while", "conditional", "call")


def short_name(name):
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``: a TPU
    trace names an operation by its whole HLO line."""
    return name.split(" = ", 1)[0].lstrip("%")


def is_container(name):
    """Control flow that holds other operations of the same line (a
    scanned stack is one ``while``): counted for busy time, left out of
    the table of operations so that its children are not counted twice."""
    return short_name(name).split(".")[0] in CONTAINERS


def device_ops(events, device_plane=DEVICE_PLANE, ops_lines=OPS_LINES):
    """``{plane: [(name, start_ns, dur_ns)]}`` of the operations that ran
    on each device."""
    out = {}
    for plane, line, name, start, dur in events:
        if plane.startswith(device_plane) and line in ops_lines:
            out.setdefault(plane, []).append((name, start, dur))
    return out


def reduce(events, *, chips, window_s, host_spans=(), clock_offset_ns=None,
           device_plane=DEVICE_PLANE, ops_lines=OPS_LINES):
    """The traced window in numbers.

    ``busy_s`` is the union of the intervals in which an operation ran,
    averaged over the ``chips`` device planes that ran any; ``ops`` maps
    an operation's name to its summed device seconds (over all chips);
    ``idle_gaps`` attributes each gap between busy intervals of the first
    device to the innermost host span covering its middle (``host_spans``
    are ``(name, start_s, end_s)`` on the host clock, mapped to the
    trace's clock by ``clock_offset_ns`` = trace_ns - host_ns).
    """
    per_dev = device_ops(events, device_plane, ops_lines)
    planes = sorted(per_dev)[:chips]
    busy, ops, first_merged = [], {}, []
    for i, plane in enumerate(planes):
        secs, merged = union_seconds(
            (s, s + d) for _, s, d in per_dev[plane]
        )
        busy.append(secs)
        if i == 0:
            first_merged = merged
        for name, _, d in per_dev[plane]:
            if is_container(name):
                continue
            key = short_name(name)
            if "custom-call" in name or "custom_call" in name:
                # a kernel: keep what names it (its target and its call's
                # own name sit in the HLO line), so a reader finds it
                key = f"{key} {_kernel_label(name)}"
            ops[key] = ops.get(key, 0.0) + d / 1e9
    gaps = {}
    if first_merged and clock_offset_ns is not None:
        spans = sorted(
            ((a * 1e9 + clock_offset_ns, b * 1e9 + clock_offset_ns, n)
             for n, a, b in host_spans),
            key=lambda t: t[0],
        )
        for (_, e0), (s1, _) in zip(first_merged, first_merged[1:]):
            mid, length = (e0 + s1) / 2, (s1 - e0) / 1e9
            owner, width = "(no host span)", None
            for a, b, n in spans:
                if a > mid:
                    break
                if b >= mid and (width is None or b - a < width):
                    owner, width = n, b - a
            gaps[owner] = gaps.get(owner, 0.0) + length
    modules = {}
    for plane, line, name, _, dur in events:
        if plane in planes and line == MODULES_LINE:
            key = name.split("(")[0]
            modules[key] = modules.get(key, 0.0) + dur / 1e9
    return {
        "modules": modules,
        "busy_s": sum(busy) / max(len(busy), 1) if busy else 0.0,
        "window_s": window_s,
        "devices": len(planes),
        "ops": ops,
        "idle_gaps": gaps,
    }


def _kernel_label(name):
    found = re.findall(r'(?:custom_call_target|kernel_name|name)="([^"]+)"',
                       name)
    return "/".join(dict.fromkeys(found)) or "custom-call"


def top(mapping, n=10):
    return [
        [k, v] for k, v in sorted(
            mapping.items(), key=lambda kv: kv[1], reverse=True
        )[:n]
    ]


def kernel_seconds(ops, needle):
    """Summed device seconds of the operations whose name holds
    ``needle`` (a Pallas kernel's ``name``), and how many names matched."""
    hits = {k: v for k, v in ops.items() if needle in k}
    return sum(hits.values()), len(hits)


def mark_offset_ns(events, host_mark_s, mark=MARK):
    """trace_ns - host_ns, from the ``perfbench.mark`` annotation the
    harness writes while it reads the host clock."""
    for plane, _, name, start, _ in events:
        if name == mark and plane.startswith("/host:"):
            return start - host_mark_s * 1e9
    return None


def keep_sample(events, cell, per_line=400):
    """With ``PERFBENCH_KEEP_TRACE=1``, write the first events of every
    line to ``chiprun_out/trace_samples/<cell>.json`` — a small recorded
    trace to look at by hand and to keep among the tests."""
    import json

    if os.environ.get("PERFBENCH_KEEP_TRACE") != "1":
        return
    seen, sample = {}, []
    for ev in events:
        key = ev[:2]
        seen[key] = seen.get(key, 0) + 1
        if seen[key] <= per_line:
            sample.append(ev)
    out = os.path.join(cell.root, "chiprun_out", "trace_samples")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"{cell.name}.json"), "w") as f:
        json.dump({"counts": {f"{a}|{b}": n for (a, b), n in seen.items()},
                   "events": sample}, f)


def host_spans(tracer):
    """The program's spans (``runtime/tracing.py``) as ``(name, start_s,
    end_s, args)`` on the host's ``perf_counter`` clock."""
    t0 = tracer._t0  # the tracer's zero on the perf_counter clock
    out = []
    for ev in list(tracer._events):
        if ev.get("ph") == "X":
            a = t0 + ev["ts"] / 1e6
            out.append(
                (ev["name"], a, a + ev["dur"] / 1e6, ev.get("args", {}))
            )
    return out


def reduce_run(run, traced, spans):
    """Read the profile a traced run left in ``run.out_dir()``, reduce
    it, and delete it. ``traced`` is ``(host_mark_s, start_s, stop_s)``."""
    import shutil
    import sys

    mark, t_start, t_stop = traced
    events = read_xplane(
        find_xplane(run.out_dir()), planes=(DEVICE_PLANE, "/host:")
    )
    for key, (n, names) in sorted(summarize_lines(events).items()):
        names = [x[:120] for x in names]
        print(f"trace line {key}: {n} events, e.g. {names}", file=sys.stderr)
    keep_sample(events, run.cell)
    out = reduce(
        events, chips=run.cell.chips, window_s=t_stop - t_start,
        host_spans=[(n, a, b) for n, a, b, _ in spans],
        clock_offset_ns=mark_offset_ns(events, mark),
    )
    out["span"] = (t_start, t_stop)
    shutil.rmtree(run.out_dir(), ignore_errors=True)
    return out


def idle_share(reduced):
    """1 - busy/window of a reduced trace, in percent; None where no
    operation was read (never 0 for nothing)."""
    if not reduced or not reduced["busy_s"]:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
