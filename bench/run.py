#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics are read from
``BENCHMARK.json`` and the files it names under ``bench/``.  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a few steady seconds are recorded with the JAX profiler and
the result carries its per-layer metrics, ``busy_s``/``window_s`` and a
``breakdown``.  The last line of standard output is one JSON object; the
numbers that decided ``correct`` are its last key, ``checks``, and the
last lines of standard error.

Exits 3, printing no result, when JAX's first device is not a TPU or
there are fewer chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import types

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from harness import device as device_lib  # noqa: E402
from harness import flops, manifest, xtrace  # noqa: E402

EXIT_NO_CHIP = 3


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(cell: manifest.Cell, tr: dict, peak: dict) -> dict:
    """Each per-layer metric of the cell, from its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    ctx = types.SimpleNamespace(
        **tr, peak=peak, flops=flops,
        programs=xtrace.time_by_name(tr["trace"], xtrace.MODULES_LINE),
        ops=xtrace.time_by_name(tr["trace"], xtrace.OPS_LINE))
    out = {}
    for m in cell.per_layer:
        value = manifest.metric_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: manifest.Cell, seed: int, seconds: float, trace: bool,
             devices) -> dict:
    """Drive the cell once and assemble its result line (a dict)."""
    log_dir = os.path.join(ROOT, ".bench_cache", "trace", cell.name)
    drv = manifest.driver(cell.config)
    res = drv.run(cell, seed, seconds, trace, devices, T_START, log_dir)
    dev = dict(res["device"])
    result = {"correct": bool(res["correct"]),
              "attempted": int(res["attempted"]),
              "failed": int(res["failed"])}
    if trace:
        tr = res.get("trace")
        if tr is None:
            raise RuntimeError("the traced window recorded no trace")
        peak = manifest.peaks(dev["kind"])
        result["metrics"] = per_layer(cell, tr, peak)
        dev["busy_s"] = xtrace.busy_s(tr["trace"])
        dev["window_s"] = tr["trace"].window_s
        result["device"] = dev
        result["breakdown"] = xtrace.breakdown(tr["trace"], tr["spans"])
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        # a tail over failed requests is infinite: JSON has no such number,
        # so it is null (such a run has failed requests, so is not correct)
        result["metrics"] = {
            k: {"value": float(v) if math.isfinite(v) else None,
                "unit": units[k]}
            for k, v in res["e2e"].items() if k in units}
        missing = sorted(set(units) - set(result["metrics"]))
        if missing:
            raise RuntimeError(f"cell {cell.name} reports no {missing}")
        result["device"] = dev
    notes = dict(res.get("notes", {}))
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in res["checks"]}
    result["_notes"] = notes
    return result


def emit(result: dict) -> None:
    notes = result.pop("_notes", {})
    for k, v in notes.items():
        print(f"note {k} = {v}", file=sys.stderr)
    for k, v in result["checks"].items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = manifest.resolve_cell(manifest.load_manifest(), args.workload)
    device_lib.COUNTER = device_lib.configure_jax()
    try:
        devices = device_lib.require_chips(cell.chips)
    except device_lib.NoAccelerator as err:
        print(f"no result: {err}", file=sys.stderr)
        return EXIT_NO_CHIP
    emit(run_cell(cell, args.seed, args.seconds, bool(args.trace), devices))
    return 0


if __name__ == "__main__":
    sys.exit(main())
