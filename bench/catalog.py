"""Find a cell's pieces by name.

Everything that belongs to one configuration, one traffic mix, one
cell or one metric is a file of its own under the benchmark directory,
and nothing here lists them:

  configs/<config>.json   the deployment as it is run; its
                          ``"tenant"`` names the file below
  tenants/<tenant>.py     what the deployment runs: its weights,
                          ``deploy()``, traffic, measured window,
                          check, work count and the size a CPU test
                          rehearses it at (``tenants/sensor.py`` lists
                          the functions)
  traffic/<mix>.json      parameters of the general traffic generator
  cells/<cell>.json       optional per-cell overrides of the mix (a
                          fixed offered rate, for instance)
  metrics/<metric>.py     a reader with ``read(ctx) -> float | None``

A new configuration, tenant kind, mix or metric is therefore a new file
plus a new entry in ``BENCHMARK.json``, with no edit to an existing
file.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict[str, Any]:
    """The ``BENCHMARK.json`` at the root of the checkout."""
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for entry in bench["workloads"]:
        if entry["name"] == name:
            return entry
    known = sorted(e["name"] for e in bench["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(known: {known})")


def config(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    return _load_json(os.path.join(bench_dir, "configs", f"{name}.json"))


def mix(name: str, cell_name: Optional[str] = None,
        bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    """The traffic mix's parameters, with the cell's own overrides
    (``cells/<cell>.json``, when there is one) laid over them."""
    params = _load_json(os.path.join(bench_dir, "traffic", f"{name}.json"))
    if cell_name is not None:
        path = os.path.join(bench_dir, "cells", f"{cell_name}.json")
        if os.path.exists(path):
            params.update(_load_json(path))
    return params


def _load_module(path: str, kind: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module     # dataclasses look their module up
    spec.loader.exec_module(module)
    return module


def reader(metric: str, bench_dir: str = BENCH_DIR):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``. Metric
    names carry dots, so the file is loaded by path, not imported."""
    path = os.path.join(bench_dir, "metrics", f"{metric}.py")
    return _load_module(path, "metric", metric).read


def tenant(name: Optional[str], bench_dir: str = BENCH_DIR):
    """The module ``tenants/<name>.py``, loaded by path like a reader;
    no name, or a name with no file, is an error, never a default."""
    folder = os.path.join(bench_dir, "tenants")
    path = os.path.join(folder, f"{name}.py")
    if not name or not os.path.isfile(path):
        known = sorted(f[:-3] for f in os.listdir(folder)
                       if f.endswith(".py")) if os.path.isdir(folder) else []
        raise KeyError(f"no tenant {name!r} in tenants/ (a configuration "
                       f"names its tenant under \"tenant\"; known: "
                       f"{known})")
    return _load_module(path, "tenant", name)


def metrics_for(bench: Dict[str, Any], cell_name: str,
                kind: str) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports:
    those without a ``workloads`` list, and those whose list names
    it."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; a kind that is
    not in the table is an error, never a default."""
    table = _load_json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
