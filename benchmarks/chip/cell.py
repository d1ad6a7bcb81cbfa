"""Cells, configurations, traffic mixes and metric readers, found by name.

``BENCHMARK.json`` at the checkout's root names each cell
``<config>.<traffic>``.  Everything else is a file found by its name:

* ``configs/<config>.json``   one deployment (schema, queries, stream,
  tenants and sessions, warm-up length, the serving settings, guarantees);
* ``traffic/<traffic>.json``  one mix (its loop: closed);
* ``queries/<builder>.py``    ``build(**args)`` -> plain query specs;
* ``generators/<name>.py``    ``generate(n_types, n_attrs, seed, **args)``
  -> stream arrays;
* ``metrics/<metric>.py``     ``read(ctx)`` -> a number, or None where the
  cell has nothing to read.

A later cell, deployment, mix or metric is a new file and a new entry in
``BENCHMARK.json``; no file here changes.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_json(kind: str, name: str, base: Path = HERE) -> dict:
    path = base / kind / f"{name}.json"
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, base: Path = HERE):
    """Import ``<base>/<kind>/<name>.py``; its directory goes on the path
    so that modules of one kind may share code."""
    d = str(base / kind)
    if d not in sys.path:
        sys.path.insert(0, d)
    key = f"chipbench_{kind}_{name}"
    mod = sys.modules.get(key)
    if mod is None:
        path = base / kind / f"{name}.py"
        spec = importlib.util.spec_from_file_location(key, path)
        if spec is None:
            raise FileNotFoundError(path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT, base: Path = HERE,
              overrides: dict | None = None) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    config = load_json("configs", w["config"], base)
    traffic = load_json("traffic", w["traffic"], base)
    for k, v in (overrides or {}).items():
        (traffic if k in traffic else config)[k] = v
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], traffic_name=w["traffic"],
                config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, workload)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, workload)])


@dataclass
class Deployment:
    """A configuration made concrete from a seed: queries, stream and the
    split of the stream over sessions."""

    types: tuple
    attrs: tuple
    specs: list
    stream: dict              # type_id, time, attrs, group, seq
    pane: int
    tenants: int
    groups_per_tenant: int
    sessions: int

    def session_tenant(self, i: int) -> int:
        return i % self.tenants

    def session_index(self, i: int) -> np.ndarray:
        """Stream positions session ``i`` sends: its tenant's events,
        stride-split over the tenant's sessions."""
        t = self.session_tenant(i)
        per = self.sessions // self.tenants
        g = self.stream["group"]
        lo, hi = t * self.groups_per_tenant, (t + 1) * self.groups_per_tenant
        idx = np.flatnonzero((g >= lo) & (g < hi))
        return idx[i // self.tenants::per]

    def within(self) -> dict:
        return {q["name"]: q["within"] for q in self.specs}


def deployment(config: dict, seed: int, base: Path = HERE) -> Deployment:
    from querylib import pane_ticks, validate

    types = tuple(config["schema"]["types"])
    attrs = tuple(config["schema"]["attrs"])
    qb = config["queries"]
    specs = load_module("queries", qb["builder"], base).build(**qb["args"])
    validate(specs, types, attrs)
    st = config["stream"]
    gen = load_module("generators", st["generator"], base)
    arrays = gen.generate(n_types=len(types), n_attrs=len(attrs), seed=seed,
                          ticks_per_minute=config["ticks_per_minute"],
                          minutes=config["minutes"], **st["args"])
    # the producer's sequence number is the stream position: the served
    # merge then breaks equal-time ties as the stream orders them
    arrays["seq"] = np.arange(len(arrays["time"]), dtype=np.int64)
    if config["sessions"] % config["tenants"]:
        raise ValueError("sessions must be a multiple of tenants")
    return Deployment(types=types, attrs=attrs, specs=specs, stream=arrays,
                      pane=pane_ticks(specs),
                      tenants=config["tenants"],
                      groups_per_tenant=config["groups_per_tenant"],
                      sessions=config["sessions"])
