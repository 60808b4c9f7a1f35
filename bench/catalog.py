"""Finds everything a run needs by the names in ``BENCHMARK.json``.

Nothing here lists a cell, a configuration, a traffic mix or a metric:
each is a file found by its name, so a later change adds one by adding
files and entries, and edits nothing that is here.

    BENCHMARK.json                      cells and metrics
    bench/configs/<config>.json         model configuration (sizes as run)
    bench/reference/<reference>.py      its weight layout, plain reference
                                        forward and output head
    bench/traffic/<traffic>.json        traffic-mix parameters
    bench/cells/<workload>.json         engine settings of one cell
    bench/metrics/<metric>.py           reader of one metric: read(run)

A new configuration brings ``configs/<c>.json``, which names the
program's registry entry (``arch``), the sizes put into it (``model``),
the serving dtype (``weights_dtype``) and its reference module
(``reference``); and, unless an existing module fits, that module,
``reference/<r>.py``, which is the one place that knows the
configuration's weights and head:

    shapes(model) -> {path: (shape, std)}   every leaf, in the program's
                                            layout; ``weights.make`` draws it
    hidden(params, tokens, model, fp8)      final normed hidden states
                                            (B, T, D), float32; ``fp8`` is
                                            the control's precision
    head_stats(params, h, targets)          (best logit, logit of target)
                                            per row of ``h``
    head_argmax_fp8(params, h)              the control's choice per row
    HEAD_BLOCK                              rows a head block takes

It reads what its maths needs from ``model`` and picks its own output
head from ``params``, and imports nothing of the program.  Its cells
bring ``traffic/``, ``cells/`` and ``metrics/`` files.  ``work.py``
counts the work of a dense decoder (every matmul parameter once per
token); a configuration that is not one (experts, latent attention)
computes its per-layer metrics' work in its own new metric files.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    end_to_end: bool
    workloads: Optional[List[str]]

    def applies_to(self, workload: str) -> bool:
        return self.workloads is None or workload in self.workloads


@dataclasses.dataclass
class Cell:
    """One workload entry with everything it names, loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    settings: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import the Python file at ``path`` as a module named ``name``
    (metric files carry dots in their names, so no plain import)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Catalog:
    def __init__(self, root: str, bench_dir: str = BENCH_DIR):
        self.root = root
        self.bench_dir = bench_dir
        self.benchmark = _load_json(os.path.join(root, "BENCHMARK.json"))
        self._readers: Dict[str, Callable] = {}

    def _path(self, *parts: str) -> str:
        return os.path.join(self.bench_dir, *parts)

    def metrics(self) -> List[Metric]:
        out = []
        for kind, e2e in (("end_to_end", True), ("per_layer", False)):
            for m in self.benchmark[kind]:
                out.append(Metric(m["name"], m["unit"], e2e,
                                  m.get("workloads")))
        return out

    def cell(self, workload: str) -> Cell:
        entries = {w["name"]: w for w in self.benchmark["workloads"]}
        if workload not in entries:
            raise KeyError(f"unknown workload {workload!r}; known: "
                           f"{sorted(entries)}")
        w = entries[workload]
        confs = {c["name"]: c for c in self.benchmark["configs"]}
        config = _load_json(os.path.join(self.root, confs[w["config"]]["file"]))
        traffic = _load_json(self._path("traffic", w["traffic"] + ".json"))
        settings = _load_json(self._path("cells", workload + ".json"))
        ms = [m for m in self.metrics() if m.applies_to(workload)]
        return Cell(workload, int(w["chips"]), config, traffic, settings,
                    [m for m in ms if m.end_to_end],
                    [m for m in ms if not m.end_to_end])

    def reference(self, config: dict):
        name = config["reference"]
        return load_module(self._path("reference", name + ".py"),
                           f"bench_reference_{name}")

    def reader(self, metric: str) -> Callable:
        if metric not in self._readers:
            mod = load_module(self._path("metrics", metric + ".py"),
                              "bench_metric_" + metric.replace(".", "_")
                              .replace("-", "_"))
            self._readers[metric] = mod.read
        return self._readers[metric]
