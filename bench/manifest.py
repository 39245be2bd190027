"""The benchmark's data: `BENCHMARK.json` and the files it names.

A cell is one entry of `workloads`.  Its configuration is the file that the
`configs` entry of the same name points at; its traffic is
`bench/traffic/<traffic>.json`; each per-layer metric is the reader
`bench/metrics/<name>.py`.  Nothing here knows a cell by name, so a new
cell, configuration or metric is a new file and a new manifest entry.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic file's contents
    end_to_end: tuple     # the manifest's metric entries, all of them
    per_layer: tuple

    def lane_seeds(self, seed: int) -> list:
        """The lanes' seeds: the run's seed plus the traffic's offsets,
        kept inside the 31 bits a lane key is made from."""
        return [(seed + off) % 2**31 for off in self.traffic["seed_offsets"]]

    def lane_rates(self) -> list:
        """The lanes' offered rates, flits/cycle/chip: the traffic's
        `offered`, one rate for every lane, or a list with one per
        entry of `seed_offsets`."""
        offered = self.traffic["offered"]
        n = len(self.traffic["seed_offsets"])
        if not isinstance(offered, list):
            return [float(offered)] * n
        if len(offered) != n:
            raise ValueError(f"{self.name}: {len(offered)} offered rates "
                             f"for {n} seed offsets")
        return [float(r) for r in offered]

    def lanes(self, seed: int) -> list:
        """The lanes as (offered, seed) pairs."""
        return list(zip(self.lane_rates(), self.lane_seeds(seed)))


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, manifest: str = MANIFEST) -> Cell:
    """The cell `name` of `manifest`, with its configuration and traffic
    files read.  Paths in the manifest are relative to its directory;
    traffic files sit in `bench/traffic/` beside the configurations'
    directory."""
    m = _read(manifest)
    base = os.path.dirname(os.path.abspath(manifest))
    cells = {w["name"]: w for w in m["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {manifest}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in m["configs"]}
    cfg_path = os.path.join(base, configs[w["config"]]["file"])
    traffic_dir = os.path.join(os.path.dirname(os.path.dirname(cfg_path)),
                               "traffic")
    return Cell(
        name=name, chips=int(w["chips"]), config=_read(cfg_path),
        traffic=_read(os.path.join(traffic_dir, w["traffic"] + ".json")),
        end_to_end=tuple(m["end_to_end"]), per_layer=tuple(m["per_layer"]))


def metric_reader(name: str, metrics_dir: str = os.path.join(BENCH,
                                                             "metrics")):
    """The `read(run, trace)` function of `bench/metrics/<name>.py`."""
    path = os.path.join(metrics_dir, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def experiment(cell: Cell, seed: int):
    """The cell as the program's own declarative spec: one topology, one
    routing, one traffic, the lanes' offered rates and seeds.  The spec's
    grid is rates x seeds; the session runs the cell's own (rate, seed)
    pairs (`Cell.lanes`), which that grid holds.  Step form and grant form
    are left at the program's defaults."""
    from repro.exp import (ExperimentSpec, RoutingSpec, SweepAxes,
                           TopologySpec, TrafficSpec)
    topo = cell.config["topology"]
    tr = cell.traffic
    return ExperimentSpec(
        name=cell.name,
        topologies=(TopologySpec(topo["kind"],
                                 tuple(sorted(topo["params"].items()))),),
        traffics=(TrafficSpec(tr["pattern"]),),
        routings=(RoutingSpec(**cell.config["routing"]),),
        axes=SweepAxes(rates=tuple(dict.fromkeys(cell.lane_rates())),
                       seeds=tuple(cell.lane_seeds(seed)),
                       warmup=tr["fill"], measure=tr["measure"]))
