"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration and its traffic are looked up by name in
``BENCHMARK.json`` and the files under ``chipbench/``.  The last line of
standard output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``); the last lines of standard error are the numbers compared for
``correct``, each beside its limit.  Without a TPU, with fewer chips than
the cell asks for, or outside a checkout that holds the program, it exits
non-zero and prints no result.
"""

import time

T0 = time.perf_counter()  # set-up is timed from here, before any import

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"FAIL: {ROOT} holds no checkout of the program (src/repro is "
              "missing)", file=sys.stderr)
        return 2
    # libtpu would write its logs under a fixed /tmp path: turn them off
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from chipbench import harness

    return harness.main(ROOT, args.workload, args.seed, args.seconds,
                        bool(args.trace), t0=T0)


if __name__ == "__main__":
    sys.exit(main())
