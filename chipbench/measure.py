"""Run cells one after another, one process each, and summarize the spread.

    python3 chipbench/measure.py --out <dir> --seconds <s> \
        <cell>:<seed>[:<trace>] [<cell>:<seed>[:<trace>] ...] [/ <run> ...]

Each run is ``chipbench/run.py`` in a process of its own (this parent never
touches JAX, so each child has the chip to itself).  Its standard output and
error go to ``<dir>/<n>_<cell>_<seed>_<trace>.{out,err}``, ``<n>`` its place
in the list.  A ``/`` closes a set of runs.  At the end, every end-to-end
metric of every cell is printed, per set, with its runs' median and the
spread of ``statistics.quantiles(n=4)`` (third minus first quartile, over
the median) -- the number the bounds of ``BENCHMARK.json`` are set from.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spread(values: list[float]) -> float:
    if len(values) < 2:
        return float("nan")
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("runs", nargs="+")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    results: dict = {}
    group = 1
    for n, spec in enumerate(args.runs):
        if spec == "/":
            group += 1
            continue
        cell, seed, *rest = spec.split(":")
        trace = rest[0] if rest else "0"
        base = os.path.join(args.out, f"{n:02d}_{cell}_{seed}_{trace}")
        t0 = time.time()
        with open(base + ".out", "w") as o, open(base + ".err", "w") as e:
            rc = subprocess.call(
                [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
                 "--workload", cell, "--seed", seed, "--seconds",
                 str(args.seconds), "--trace", trace], stdout=o, stderr=e,
                cwd=ROOT)
        wall = time.time() - t0
        line = None
        with open(base + ".out") as o:
            lines = [x for x in o.read().splitlines() if x.strip()]
        if lines:
            try:
                line = json.loads(lines[-1])
            except json.JSONDecodeError:
                line = None
        if line is None:
            print(f"{spec}: rc={rc} wall={wall:.1f}s NO RESULT", flush=True)
            continue
        m = {k: v["value"] for k, v in line["metrics"].items()}
        print(f"{spec}: rc={rc} wall={wall:.1f}s correct={line['correct']} "
              f"metrics={m} checks={line.get('checks')} "
              f"device={line['device']}", flush=True)
        if trace == "0":
            for k, v in m.items():
                results.setdefault((cell, k, group), []).append(v)
    for (cell, k, group), vals in sorted(results.items()):
        print(f"SPREAD {cell} {k} set {group}: n={len(vals)} median="
              f"{statistics.median(vals):.6g} spread={spread(vals):.4%} "
              f"values={vals}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
