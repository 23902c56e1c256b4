"""The control of a cell's ``correct``: a whole run of the cell with the
precision below the configuration's (bfloat16 for float32) in the program's
place, judged by the run's own check.

    python3 chipbench/control.py --workload <cell> --seeds <n>,<n>,... \
        --seconds <s>

Each seed is one run of the cell through the harness (set-up, a window of
``--seconds`` at the cell's own load, release, check), with the program's
entry point replaced by the cell's control (``control`` of its driver: the
program's own lower-precision path where it has one, else the reference in
bfloat16 in its step's place).  Prints, for each seed, ``correct`` and every
number compared beside its limit; ``correct`` has to come out false, by a
compared number and not only by what the stand-in compiles inside the
window.  Runs on the chip at the cell's own size; the benchmark's own runs
never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: checks of the run itself, not numbers compared with the reference
RUN_CHECKS = ("window_compiles", "program_cache_growth")


def run_control(root: str, cell_name: str, seed: int, seconds: float, xp,
                dtype, **kw) -> dict:
    """One run of ``cell_name`` with its control in the program's place."""
    from chipbench import harness

    cell = harness.resolve(root, cell_name, kw.get("bench"),
                           kw.get("traffic_dir"))
    patches = cell.driver.control(cell, xp, dtype)
    saved = [(mod, name, getattr(mod, name)) for mod, name, _ in patches]
    try:
        for mod, name, standin in patches:
            setattr(mod, name, standin)
        return harness.run_cell(root, cell_name, seed, seconds, False,
                                t0=time.perf_counter(), **kw)
    finally:
        for mod, name, real in saved:
            setattr(mod, name, real)


def failing(out: dict) -> list[str]:
    """The compared numbers of a result line that fail their limits."""
    return [k for k, c in out["checks"].items()
            if k not in RUN_CHECKS and not c["value"] <= c["limit"]]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax.numpy as jnp

    from chipbench import harness

    for seed in (int(s) for s in args.seeds.split(",")):
        out = run_control(  # tracecheck: disable=TC005 -- the control
            ROOT, args.workload, seed, args.seconds, jnp, jnp.bfloat16)
        print(json.dumps(harness.finite(
            {"workload": args.workload, "seed": seed,
             "correct": out["correct"], "attempted": out["attempted"],
             "failed": out["failed"], "fails": failing(out),
             "checks": out["checks"], "device": out["device"]})),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
