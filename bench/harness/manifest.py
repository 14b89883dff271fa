"""The benchmark's manifest and the files it names, found by name.

``BENCHMARK.json`` lists configurations, cells (``workloads``) and
metrics.  Everything that belongs to one of them lives in a file of its
own under ``bench/``:

* configuration ``<c>``:      ``bench/configs/<c>.json`` (as the manifest's
  ``file`` says), with its plain reference module beside it;
* traffic mix ``<t>``:        ``bench/traffic/<t>.json``;
* per-layer metric ``<m>``:   ``bench/metrics/<m>.py`` (a ``read(ctx)``);
* driver ``<d>``:             ``bench/drivers/<d>.py`` (named by the
  configuration's ``driver`` key);
* peaks of a device kind:     ``bench/peaks.json``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys
from types import ModuleType
from typing import Any

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of the manifest, resolved to its files."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]        # the e2e metrics this cell reports
    per_layer: list[dict]         # the per-layer metrics this cell reports


def load_json(path: str) -> Any:
    with open(path, "r", encoding="utf-8") as fh:
        return json.load(fh)


def load_manifest(path: str = MANIFEST) -> dict:
    return load_json(path)


def _applies(metric: dict, cell_name: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell_name in cells


def resolve_cell(manifest: dict, name: str) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    entry = configs[w["config"]]
    config = load_json(os.path.join(ROOT, entry["file"]))
    config["_dir"] = os.path.dirname(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     w["traffic"] + ".json"))
    e2e = [m for m in manifest["end_to_end"] if _applies(m, name)]
    per_layer = [m for m in manifest["per_layer"] if _applies(m, name)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def load_module(path: str, name: str) -> ModuleType:
    """Import the Python file at ``path`` under module name ``name``
    (once per process)."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def driver(config: dict) -> ModuleType:
    name = config["driver"]
    return load_module(os.path.join(BENCH_DIR, "drivers", name + ".py"),
                       f"bench_driver_{name}")


def reference(config: dict) -> ModuleType:
    """The configuration's plain reference, beside its file."""
    name = config["reference"]
    return load_module(os.path.join(config["_dir"], name + ".py"),
                       f"bench_ref_{name}")


def metric_reader(name: str) -> ModuleType:
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    return load_module(path, "bench_metric_" + re.sub(r"\W", "_", name))


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; a kind that is not in
    the table is an error, never a default."""
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    kinds = table["devices"]
    if device_kind not in kinds:
        raise KeyError(f"device kind {device_kind!r} has no row in "
                       f"bench/peaks.json (have {sorted(kinds)})")
    return kinds[device_kind]
