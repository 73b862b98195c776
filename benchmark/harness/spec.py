"""What a cell is, read from data: ``BENCHMARK.json`` names the cell, the
configuration and the traffic mix; everything that belongs to one of them
is a file found by that name under the benchmark's directory.

    configs/<config>.json            sizes, sources, pins, rehearsal sizes
    traffic/<mix>.json               generator kind + parameters
    cells/<cell>.json                what belongs to the pair: pass depth,
                                     passes a window, the AUC floor
    generators/<kind>.py             run(ctx) -> Measured
    models/<config>.py               build(config) -> the program's model
    reference/<config>.py            logit(params, pooled, dense): the tower
    e2e_metrics/<metric>.py          read(run) -> number | None
    layer_metrics/<metric>.py        read(run) -> number | None

A later change adds a cell by adding files and entries; nothing here
names a configuration, a mix or a metric.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(ValueError):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """The module ``<bench_dir>/<kind>/<name>.py``, loaded by path (metric
    names hold dots, so they are not importable names)."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.exists(path):
        raise SpecError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""

    def __init__(self, workload: str, bench_json: Optional[str] = None,
                 bench_dir: str = BENCH_DIR):
        self.bench_dir = bench_dir
        self.spec = load_json(bench_json
                              or os.path.join(os.path.dirname(bench_dir),
                                              "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if workload not in cells:
            raise SpecError(f"unknown workload {workload!r}; "
                            f"BENCHMARK.json has {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        self.config_name = self.entry["config"]
        self.traffic_name = self.entry["traffic"]
        conf = {c["name"]: c for c in self.spec["configs"]}
        root = os.path.dirname(bench_dir)
        self.config = load_json(os.path.join(
            root, conf[self.config_name]["file"]))
        self.traffic = load_json(os.path.join(
            bench_dir, "traffic", self.traffic_name + ".json"))
        self.params = load_json(os.path.join(
            bench_dir, "cells", workload + ".json"))

    def metrics(self, group: str) -> list:
        """The metric entries of ``end_to_end`` / ``per_layer`` that this
        cell reports (an entry with ``workloads`` lists its cells)."""
        return [m for m in self.spec[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def module(self, kind: str, name: str):
        return load_module(kind, name, self.bench_dir)

    def sized(self, rehearse: bool) -> dict:
        """The configuration as it is run: the file's sizes, or under
        ``--rehearse`` the file with its ``rehearsal`` block laid over."""
        if not rehearse:
            return self.config
        cfg = dict(self.config)
        for key, value in self.config["rehearsal"].items():
            if isinstance(value, dict) and isinstance(cfg.get(key), dict):
                cfg[key] = {**cfg[key], **value}
            else:
                cfg[key] = value
        return cfg

    def param(self, key: str, rehearse: bool = False):
        """A parameter of the pair, from ``cells/<cell>.json``: what fits
        neither the configuration (it differs by mix) nor the mix (it
        differs by configuration).  Under ``--rehearse`` the file's
        ``rehearsal`` block wins where it has the key."""
        if rehearse and key in self.params.get("rehearsal", {}):
            return self.params["rehearsal"][key]
        if key not in self.params:
            raise SpecError(f"cells/{self.name}.json has no {key!r}")
        return self.params[key]
