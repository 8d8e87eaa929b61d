"""Finding a cell's files by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: a later PR adds ``workloads/<cell>.json``, ``traffic/<mix>.json``,
``configs/<configuration>.json`` with ``references/<configuration>.py``,
``metrics/<metric>.py`` and the entries in ``BENCHMARK.json``, and edits
no file that is there.
"""

import importlib
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


class CellError(Exception):
    """The benchmark's files do not describe the cell that was asked for."""


def _load_json(path):
    if not os.path.isfile(path):
        raise CellError(f"missing file: {os.path.relpath(path, ROOT)}")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_benchmark(root=ROOT):
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def load_module(path, name=None):
    """Import a python file by path (metric and reference files carry
    '.' and '-' in their names, which ``import`` cannot spell)."""
    if not os.path.isfile(path):
        raise CellError(f"missing file: {os.path.relpath(path, ROOT)}")
    name = name or "perfbench_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT)
    )
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything its names lead to."""

    def __init__(self, name, root=ROOT, bench_dir=HERE):
        self.root, self.bench_dir = root, bench_dir
        self.benchmark = load_benchmark(root)
        entries = {w["name"]: w for w in self.benchmark["workloads"]}
        if name not in entries:
            raise CellError(
                f"no workload {name!r} in BENCHMARK.json "
                f"(it has {sorted(entries)})"
            )
        self.entry = entries[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.spec = _load_json(
            os.path.join(bench_dir, "workloads", f"{name}.json")
        )
        self.traffic = _load_json(os.path.join(
            bench_dir, "traffic", f"{self.entry['traffic']}.json"
        ))
        configs = {c["name"]: c for c in self.benchmark["configs"]}
        centry = configs[self.entry["config"]]
        self.config = _load_json(os.path.join(root, centry["file"]))
        self.kind = self.spec["kind"]

    # -- what the names lead to -------------------------------------------
    def family(self):
        return importlib.import_module(
            f"perfbench.families.{self.config['family']}"
        )

    def reference(self):
        return load_module(os.path.join(self.root, self.config["reference"]))

    def kind_module(self):
        return importlib.import_module(f"perfbench.harness.kind_{self.kind}")

    def end_to_end(self):
        """The end-to-end metrics this cell reports, in file order."""
        return [
            m for m in self.benchmark["end_to_end"]
            if "workloads" not in m or self.name in m["workloads"]
        ]

    def per_layer(self):
        """The per-layer metrics whose readers run in this cell: those
        that list it, and those that list no cell but move an
        end-to-end metric this cell reports."""
        mine = {m["name"] for m in self.end_to_end()}
        out = []
        for m in self.benchmark["per_layer"]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif m["moves"] in mine:
                out.append(m)
        return out

    def metric_reader(self, metric_name):
        return load_module(
            os.path.join(self.bench_dir, "metrics", f"{metric_name}.py")
        )
