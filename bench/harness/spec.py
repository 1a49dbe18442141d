"""Find a cell's configuration, traffic, limits and metric readers by name.

``BENCHMARK.json`` at the checkout's root names every cell, configuration
and metric.  Each of them lives in a file of its own under ``bench/``:

* ``configs/<config>.json``: the deployment (reference, engine, server
  settings, the guarantees it states);
* ``traffic/<traffic>.json``: the parameters the one traffic generator
  (``harness.traffic``) reads;
* ``cells/<workload>.json``: the limits of the numbers that decide
  ``correct`` in that cell, with the readings they were set from;
* ``metrics/<metric>.py``: a reader ``read(ctx) -> float | None``.

A new cell, mix or metric is new files and entries; no code changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    """Everything the harness knows about one workload of the benchmark."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


def _applies(metric: dict, workload: str, reported: set | None) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return reported is None or metric.get("moves") in reported


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` with its files; raises KeyError/OSError when a
    name or a file is missing.  A cell written as data but kept out of
    ``BENCHMARK.json`` (``cells/<config>.<traffic>.json``, for the tools
    and tests) loads too, with its configuration and traffic found by
    the two parts of its name."""
    spec = load_benchmark(root)
    bench = root / "bench"
    wl = {w["name"]: w for w in spec["workloads"]}.get(name)
    if wl is None:
        if not (bench / "cells" / f"{name}.json").exists():
            raise KeyError(name)
        config, traffic = name.split(".", 1)
        wl = {"name": name, "config": config, "traffic": traffic,
              "chips": 1}
    files = {c["name"]: c["file"] for c in spec["configs"]}
    config = _load_json(root / files.get(
        wl["config"], f"bench/configs/{wl['config']}.json"))
    traffic = _load_json(bench / "traffic" / f"{wl['traffic']}.json")
    limits = _load_json(bench / "cells" / f"{name}.json")["limits"]
    e2e = [m for m in spec["end_to_end"] if _applies(m, name, None)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _applies(m, name, reported)]
    return Cell(name=name, chips=int(wl["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer)


def metric_reader(name: str, bench: pathlib.Path = BENCH):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = bench / "metrics" / f"{name}.py"
    modname = "bench_metric_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
