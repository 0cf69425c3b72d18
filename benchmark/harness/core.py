"""What every cell shares: the manifest, the record of one run, the readers.

The harness is driven by data. ``BENCHMARK.json`` names cells, their
configurations, traffic mixes and metrics; each of those is a file of its
own that is found by that name:

    configs/<config>.json     the sizes as run; "family" names models/<family>.py
    traffic/<traffic>.json    the mix's parameters; "kind" names drivers/<kind>.py
    metrics/<metric>.py       read(run) -> number, or None where there is nothing to read
    kernels/<kernel>.py       a kernel's operations and bytes per call, from shapes
    limits/<cell>.json        the limits that decide `correct`, each with its readings

so a later PR adds a cell by adding files and one ``workloads`` entry.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)


class Steer:
    """A test's steering, passed in Python only: no flag and no
    environment variable reaches it. ``manifest`` and ``root`` point a
    rehearsal at its own tiny cell; ``allow_cpu`` skips the look for a
    chip; ``break_program(obj)`` plants a fault in the program's object
    once it is built; ``lower_precision`` runs the control."""

    def __init__(self, manifest=None, root=None, allow_cpu=False,
                 break_program=None, lower_precision=False):
        self.manifest = manifest
        self.root = root
        self.allow_cpu = allow_cpu
        self.break_program = break_program
        self.lower_precision = lower_precision


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    name = "bench_" + os.path.relpath(path, HERE).replace(os.sep, "_") \
        .replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


class Run:
    """One run of one cell: what the driver fills in and the readers
    read. Times are ``time.perf_counter`` seconds."""

    def __init__(self, manifest, cell_name, seed, seconds, trace, steer,
                 root, t_start):
        cells = {c["name"]: c for c in manifest["workloads"]}
        if cell_name not in cells:
            raise SystemExit(f"benchmark: no workload {cell_name!r}; "
                             f"there are {sorted(cells)}")
        self.manifest = manifest
        self.cell = cells[cell_name]
        self.root = root
        self.steer = steer or Steer()
        entry = {c["name"]: c for c in manifest["configs"]}[
            self.cell["config"]]
        config_file = os.path.join(REPO, entry["file"])
        self.traffic = load_json(self.find(
            "traffic", self.cell["traffic"] + ".json"))
        self.limits = load_json(self.find(
            "limits", self.cell["name"] + ".json"))
        self.model = load_module(os.path.join(
            HERE, "models", load_json(config_file)["family"] + ".py"))
        self.config = self.model.load_config(config_file)   # and checks it
        self.driver = load_module(os.path.join(
            HERE, "drivers", self.traffic["kind"] + ".py"))
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.chips = self.cell["chips"]
        self.t_start = t_start
        self.device = None
        self.peaks = None
        self.setup_s = None
        self.window = None          # (open, close)
        self.counters = {}
        self.spans = None
        self.trace_summary = None
        self.memory_peak_bytes = None
        self.compiles_in_window = 0
        self.attempted = 0
        self.failed = 0
        self.compared = []          # (name, value, limit)
        self.marks = [("start", t_start)]

    def mark(self, name):
        """A phase of set-up ends here (printed on standard error)."""
        self.marks.append((name, time.perf_counter()))

    def find(self, kind, name):
        """A file of the benchmark by kind and name: under a rehearsal's
        own root where it has one there, else under ``benchmark/``."""
        for root in (self.root, HERE):
            path = os.path.join(root, kind, name)
            if os.path.exists(path):
                return path
        raise FileNotFoundError(f"no {kind}/{name} under {self.root}")

    # -- the window ------------------------------------------------------
    def open_window(self, at=None):
        now = time.perf_counter() if at is None else at
        self.setup_s = now - self.t_start
        self._window_open = now
        self._counting = True
        return now

    def close_window(self, at=None):
        at = time.perf_counter() if at is None else at
        self.window = (self._window_open, at)
        self._counting = False

    @property
    def window_s(self):
        return self.window[1] - self.window[0]

    def count_compile(self, *_args, **_kw):
        if getattr(self, "_counting", False):
            self.compiles_in_window += 1

    # -- correct ---------------------------------------------------------
    def compare(self, name, value):
        """Hold one number against the cell's limit of that name."""
        limit = self.limits["limits"][name]
        self.compared.append((name, float(value), float(limit)))

    @property
    def correct(self):
        return bool(self.compared) and all(
            math.isfinite(v) and v <= lim for _, v, lim in self.compared)

    # -- metrics ---------------------------------------------------------
    def applies(self, metric):
        return self.cell["name"] in metric.get(
            "workloads", [self.cell["name"]])

    def read_metrics(self, group):
        out = {}
        for metric in self.manifest[group]:
            if not self.applies(metric):
                continue
            reader = load_module(self.find("metrics",
                                           metric["name"] + ".py"))
            value = reader.read(self)
            if value is None:
                continue
            if not math.isfinite(value):
                raise RuntimeError(f"metric {metric['name']} read {value}")
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
        return out

    def result(self):
        traced = bool(self.trace)
        device = dict(self.device, memory_peak_bytes=self.memory_peak_bytes)
        line = {"correct": self.correct, "attempted": self.attempted,
                "failed": self.failed,
                "metrics": self.read_metrics(
                    "per_layer" if traced else "end_to_end"),
                "device": device}
        if traced and self.trace_summary is not None:
            device["busy_s"] = self.trace_summary["busy_s"]
            device["window_s"] = self.trace_summary["window_s"]
            line["breakdown"] = {
                "device_ops": self.trace_summary["device_ops"],
                "idle_gaps": self.trace_summary["idle_gaps"]}
        line["compared"] = {name: {"value": value, "limit": limit}
                            for name, value, limit in self.compared}
        return line


def percentile(values, share):
    """Nearest rank: the smallest value with at least ``share`` of the
    sample at or below it."""
    ordered = sorted(values)
    if not ordered:
        return None
    return ordered[max(0, math.ceil(share * len(ordered)) - 1)]
