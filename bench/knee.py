#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest arrival rate
at which the queue does not grow across the window.

    python3 bench/knee.py --workload rwkv6-chat --rates 2,4,6,8 --seconds 20

One engine is built and warmed once; each rate then runs the cell's mix
(its lengths, ramp and drain) at that rate, and the engine is drained
before the next.  Per rate it prints the requests due in the window, how
many ended, TTFT and TBT tails, and the mean TTFT of the window's first
and last thirds: a queue that grows shows as a last third well above the
first.  The rate found is written into the mix's file by hand, at about
four fifths of the knee.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from harness import device as device_lib  # noqa: E402
from harness import manifest, stats  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = manifest.resolve_cell(manifest.load_manifest(), args.workload)
    device_lib.COUNTER = device_lib.configure_jax()
    try:
        device_lib.require_chips(cell.chips)
    except device_lib.NoAccelerator as err:
        print(f"no result: {err}", file=sys.stderr)
        return 3
    drv = manifest.driver(cell.config)
    cfgf = cell.config
    _, _, engine = drv.build(cfgf, args.seed)
    drv.warm(engine, cfgf, cell.traffic, args.seed)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate,
                   drain_s=min(float(cell.traffic["drain_s"]), 30.0))
        loop = drv.Loop(engine, args.seed, cfgf["model"]["vocab"],
                        uid_base=k * 10**6)
        w0, w1 = drv.drive_open(loop, mix, args.seconds, time.perf_counter())
        win = sorted((r for r in loop.recs.values() if r.phase == "window"),
                     key=lambda r: r.due)
        ttft = [(r.times[0] - r.due) * 1e3 if r.times else float("inf")
                for r in win]
        tbt = [(b - a) * 1e3 for r in win for a, b in zip(r.times, r.times[1:])]
        third = max(len(ttft) // 3, 1)
        first, last = (statistics.fmean(ttft[:third]),
                       statistics.fmean(ttft[-third:]))
        delivered = sum(1 for r in loop.recs.values() for t in r.times
                        if w0 <= t < w1)
        ended = sum(r.ok for r in win)
        print(json.dumps({
            "rate_per_s": rate, "due": len(win), "ended": ended,
            "ttft_p50_ms": stats.percentile(ttft, 50),
            "ttft_p95_ms": stats.percentile(ttft, 95),
            "tbt_p50_ms": stats.percentile(tbt, 50) if tbt else None,
            "tbt_p95_ms": stats.percentile(tbt, 95) if tbt else None,
            "ttft_first_third_ms": first, "ttft_last_third_ms": last,
            "output_tokens_per_s": stats.rate(delivered, w1 - w0),
            "late_p99_ms": stats.percentile(loop.lateness, 99) * 1e3,
        }), flush=True)
        # past the knee the backlog grows through the window: one such
        # rate is enough.  (A request with a long output can still be
        # running when the capped drain ends, so ``ended`` is only shown.)
        if last > 3 * first:
            break
        for _ in engine.stream():          # drain before the next rate
            pass
        engine.take_finished()
    return 0


if __name__ == "__main__":
    sys.exit(main())
