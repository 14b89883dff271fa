#!/usr/bin/env python3
"""The lower and upper readings of a cell's correctness numbers.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 10

For each seed, one run of the cell at its own size and load (a short
window) as the benchmark makes it, which gives the lower reading, then
the same run with each control put in the program's place, which gives
the upper readings.  Every run is judged by the harness's own comparison,
so a control's line shows ``correct`` false where the limit holds.

The controls are the next precision below the configuration's, the step
a later change would be tempted to take:

* a served bfloat16 model: its weight matrices rounded per output
  channel to float8 e4m3 (``fp8``) or to int8 (``int8``), served by the
  program with its own bfloat16 compute; or the reference itself with
  weights so rounded and activations in bfloat16 (``ref-fp8``,
  ``ref-int8``), read at every position of the program's served tokens;
* the float32 LSTM: the program's own path in bfloat16.

``fp8`` and ``int8`` hold a second copy of the weights beside the
program's: at rwkv6-3b's full width that does not fit one v5e, so the
control at the cell's size is ``ref-fp8``.

Prints one JSON line per run.  Limits in the configuration files are set
from these readings; the benchmark's own runs never run a control.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from harness import device as device_lib  # noqa: E402
from harness import manifest  # noqa: E402


def weights_rounded(kind: str):
    """Serve the seeded weights rounded to the ``kind`` grid."""
    def build(orig, *a):
        ref, w, engine = orig(*a)
        engine.params = ref.to_program(ref.rounded(w, kind))
        return ref, w, engine
    return "build", build


def reference_rounded(kind: str):
    """Read, at every position of the program's served requests, the gap
    of the token the reference puts first when its weights are rounded
    to the ``kind`` grid and its activations held in bfloat16."""
    def compare(orig, ref, cfgf, w, recs):
        return orig(ref, cfgf, w, recs, control=kind)
    return "compare", compare


def program_dtype(dtype: str):
    """The program's own path in ``dtype``."""
    def build(orig, *a):
        return orig(*a, dtype=dtype)
    return "build", build


#: per driver, each control as (the driver function it wraps, wrapper)
CONTROLS = {
    "slot_engine": {"fp8": weights_rounded("fp8"),
                    "int8": weights_rounded("int8"),
                    "ref-fp8": reference_rounded("fp8"),
                    "ref-int8": reference_rounded("int8")},
    "lstm_windows": {"bfloat16": program_dtype("bfloat16")},
}


@contextlib.contextmanager
def in_place(drv, control: str):
    """Within the block, the driver runs the control in the program's
    place."""
    name, wrap = CONTROLS[drv.__name__.removeprefix("bench_driver_")][control]
    orig = getattr(drv, name)
    setattr(drv, name, lambda *a: wrap(orig, *a))
    try:
        yield
    finally:
        setattr(drv, name, orig)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--controls", default=None,
                    help="comma-separated, or 'none' (default: every "
                         "control of the cell's driver)")
    ap.add_argument("--program", type=int, choices=(0, 1), default=1,
                    help="also run the program itself")
    args = ap.parse_args(argv)
    cell = manifest.resolve_cell(manifest.load_manifest(), args.workload)
    device_lib.COUNTER = device_lib.configure_jax()
    try:
        devices = device_lib.require_chips(cell.chips)
    except device_lib.NoAccelerator as err:
        print(f"no result: {err}", file=sys.stderr)
        return 3
    drv = manifest.driver(cell.config)
    controls = (list(CONTROLS[cell.config["driver"]]) if not args.controls
                else [] if args.controls == "none"
                else args.controls.split(","))
    runs = ([None] if args.program else []) + controls
    for seed in (int(s) for s in args.seeds.split(",")):
        for control in runs:
            t0 = time.perf_counter()
            with (in_place(drv, control) if control
                  else contextlib.nullcontext()):
                res = drv.run(cell, seed, args.seconds, False, devices, t0,
                              "")
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "control": control, "correct": res["correct"],
                              "checks": res["checks"], "notes": res["notes"],
                              "e2e": res["e2e"]}), flush=True)
            del res
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
