"""Find the knee of an open-loop cell: the highest offered rate it sustains.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 8,12,16,...

Runs the cell's open loop once per rate, in one process (the program is
compiled once), and prints for each rate the windows due, those emitted
inside the window, the latency median and 95th percentile, and how long the
backlog took to drain after the window closed, and the rate served (all
windows emitted over the time to the last emission, which is the
service's capacity once the offered rate is above it).  A rate is
sustained when windows due in the second half of the window wait, at the
median, no more than 1.2 times as long (plus a quarter second) as those of
the first half: the backlog does not grow.  The last line is
``knee <rate>``, the highest rate sustained with every lower rate; the
cell's traffic file offers about four fifths of it.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from chipbench import harness

    cell = harness.resolve(ROOT, args.workload)
    harness.device_info(cell.chips, require_chip=True)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    rates = [float(r) for r in args.rates.split(",")]
    sustained = []
    for rate in rates:
        traffic = dict(cell.traffic, rate_windows_per_s=rate)
        c = dataclasses.replace(cell, traffic=traffic)
        t0 = time.perf_counter()
        st = cell.driver.setup(c, args.seed, args.seconds)
        t1 = time.perf_counter()
        out = cell.driver.window(st, args.seconds,
                                 harness.Tracer(False, ""))
        n = out["notes"]
        served = n["emitted"] / (args.seconds + n["after_close_s"])
        print(f"rate {rate:g}/s: setup {t1 - t0:.1f} s, due "
              f"{n['windows_due']}, emitted in window "
              f"{n['emitted_in_window']} ({out['windows_per_s']:.2f}/s), "
              f"p50 {n['p50_ms']:.1f} ms, p95 {out['window_p95_ms']:.1f} ms, "
              f"drain after close {n['after_close_s']:.2f} s, fill "
              f"{out['counters']['fill_ratio']:.3f}, generator late p95 "
              f"{n['generator_late_p95_ms']:.1f} ms, median wait by half "
              f"{n['p50_halves_ms'][0]:.0f} / {n['p50_halves_ms'][1]:.0f} "
              f"ms, served {served:.2f}/s", flush=True)
        cell.driver.release(st)
        first, second = n["p50_halves_ms"]
        if n["windows_due"] and second <= 1.2 * first + 250.0:
            sustained.append(rate)
    knee = max((r for r in sustained if all(
        s in sustained for s in rates if s < r)), default=None)
    print(f"knee {knee}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
