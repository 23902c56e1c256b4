"""The benchmark's harness: find a cell by name, run it, report it.

Everything that belongs to one cell is found by name, so a later change
adds a cell or a metric by adding files and never edits one:

* ``BENCHMARK.json`` names each cell's configuration and traffic mix;
* ``chipbench/configs/<config>.json`` is the deployment (its ``file`` in
  ``BENCHMARK.json``);
* ``chipbench/traffic/<traffic>.json`` holds the traffic mix's parameters,
  its ``kind``, and the limits of the numbers its cells compare;
* ``chipbench/drivers/<kind>.py`` is the one generator and driver of each
  kind of traffic (``setup``, ``window``, ``check``, ``min_bytes``);
* ``chipbench/metrics/<metric>.py`` reads one per-layer metric from a
  finished :class:`Run` (``read(run)``, ``None`` when there is nothing to
  read, and the metric is then left out of the line).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Any

#: the end-to-end metric every cell reports: process start to first request
SETUP_METRIC = "setup_s"


class BenchError(RuntimeError):
    """The run cannot report a result (no chip, unknown name, bad file)."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a plug-in file (driver or metric reader) by its path."""
    if not os.path.isfile(path):
        raise BenchError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One cell, everything it needs resolved from the files by name."""

    name: str
    chips: int
    config: dict
    traffic: dict
    driver: Any
    end_to_end: list
    per_layer: list


def resolve(root: str, cell: str, bench: dict | None = None,
            traffic_dir: str | None = None) -> Cell:
    """Look up ``cell`` in ``BENCHMARK.json`` (or ``bench``) under ``root``."""
    bench = bench or load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell not in cells:
        raise BenchError(f"no cell {cell!r}; cells: {sorted(cells)}")
    w = cells[cell]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    tdir = traffic_dir or os.path.join(root, "chipbench", "traffic")
    traffic = load_json(os.path.join(tdir, w["traffic"] + ".json"))
    driver = load_module(
        os.path.join(root, "chipbench", "drivers", traffic["kind"] + ".py"),
        f"chipbench_driver_{traffic['kind']}")

    def mine(m):
        return "workloads" not in m or cell in m["workloads"]

    return Cell(name=cell, chips=int(w["chips"]), config=config,
                traffic=traffic, driver=driver,
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


class CompileCounter:
    """Counts lowerings of jitted programs (JAX's monitoring events).

    A lowering happens on every miss of a jit cache, whether or not the
    persistent compilation cache then has the binary, so a count that moves
    inside the measured window means something compiled there.
    """

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.names: list[str] = []

        def listener(event, duration, **kw):
            if event == self.EVENT:
                self.count += 1
                self.names.append(str(kw.get("fun_name", "?")))

        jax.monitoring.register_event_duration_secs_listener(listener)


class Tracer:
    """Starts and stops the profiler around a steady part of the window."""

    def __init__(self, enabled: bool, out_dir: str):
        self.enabled = enabled
        self.out_dir = out_dir
        self.t_start = self.t_stop = None
        self.requests = 0

    def start(self) -> None:
        if not self.enabled or self.t_start is not None:
            return
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # host TraceMe spans only
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.t_start = time.perf_counter()

    def stop(self, requests: int) -> None:
        """Stop after ``requests`` whole requests, all ready on the device."""
        if not self.enabled or self.t_start is None or self.t_stop is not None:
            return
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        self.requests = requests

    @property
    def active(self) -> bool:
        return self.t_start is not None and self.t_stop is None


@dataclasses.dataclass
class Run:
    """What a finished run hands the metric readers."""

    cell: Cell
    device_kind: str
    window: dict                  # end-to-end values and driver counters
    spans: dict                   # host-clock spans, name -> [seconds]
    counters: dict                # program counters, name -> number
    program_spans: dict           # the program's own timings, name -> [s]
    trace: "Any | None" = None    # chipbench.trace.Reduced of the traced part
    trace_requests: int = 0       # whole requests inside the traced part
    min_bytes: float = 0.0        # least bytes one request has to move


def device_info(chips: int, require_chip: bool) -> dict:
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if require_chip and d0.platform != "tpu":
        raise BenchError(f"JAX found no TPU (first device: {d0.platform}); "
                         "the benchmark never falls back to the CPU")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run_cell(root: str, cell_name: str, seed: int, seconds: float,
             trace: bool, *, t0: float, require_chip: bool = True,
             bench: dict | None = None, traffic_dir: str | None = None
             ) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``require_chip=False`` (tests only) runs wherever JAX runs, and
    ``bench``/``traffic_dir`` point a test at its own small files.
    """
    cell = resolve(root, cell_name, bench, traffic_dir)
    device = device_info(cell.chips, require_chip)
    if require_chip:
        import jax

        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
        # cache every program, however quick to compile, so that only the
        # first run of a cell in a checkout compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = CompileCounter()
    tracer = Tracer(trace, os.path.join(root, ".chipbench_trace", cell_name))
    drv = cell.driver
    state = drv.setup(cell, seed, seconds)
    setup_s = time.perf_counter() - t0
    program_caches_before = drv.cache_counters(state)
    compiles_before = compiles.count
    window = drv.window(state, seconds, tracer)
    compiles_in_window = compiles.count - compiles_before
    program_caches_after = drv.cache_counters(state)
    device["memory_peak_bytes"] = memory_peak(cell.chips)
    drv.release(state)
    checks, attempted, failed = drv.check(state)
    checks = [dict(name="window_compiles", value=compiles_in_window,
                   limit=0),
              dict(name="program_cache_growth",
                   value=sum(program_caches_after.values())
                   - sum(program_caches_before.values()), limit=0)] + checks
    correct = failed == 0 and all(c["value"] <= c["limit"] for c in checks)
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed)}
    metrics: dict = {}
    if not trace:
        window[SETUP_METRIC] = setup_s
        for m in cell.end_to_end:
            if m["name"] in window:
                metrics[m["name"]] = {"value": window[m["name"]],
                                      "unit": m["unit"]}
    else:
        from chipbench import trace as trace_mod

        reduced = None
        if tracer.t_stop is not None:
            reduced = trace_mod.reduce_dir(tracer.out_dir,
                                           tracer.t_stop - tracer.t_start)
            shutil.rmtree(tracer.out_dir, ignore_errors=True)
            if reduced.devices:
                device["busy_s"] = reduced.busy_s
                device["window_s"] = reduced.window_s
            top = sorted(reduced.module_s.items(), key=lambda kv: -kv[1])
            print(f"traced programs (device s): {top[:8]}", file=sys.stderr)
        run = Run(cell=cell, device_kind=device["kind"], window=window,
                  spans=window.get("spans", {}),
                  counters=window.get("counters", {}),
                  program_spans=window.get("program_spans", {}),
                  trace=reduced, trace_requests=tracer.requests,
                  min_bytes=float(drv.min_bytes(state)))
        for m in cell.per_layer:
            reader = load_module(
                os.path.join(root, "chipbench", "metrics", m["name"] + ".py"),
                "chipbench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced is not None:
            out["breakdown"] = reduced.breakdown()
    out["metrics"] = metrics
    out["device"] = device
    if compiles_in_window:
        names = compiles.names[-compiles_in_window:]
        print(f"compiled inside the window: {names}", file=sys.stderr)
    for k, v in window.get("notes", {}).items():
        print(f"{k}: {v}", file=sys.stderr)
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return out


def finite(x):
    """The result line as strict JSON: a non-finite number becomes a
    string (``"inf"``, ``"nan"``), which only a failed run can hold."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    return x


def main(root: str, cell: str, seed: int, seconds: float, trace: bool, *,
         t0: float) -> int:
    try:
        out = run_cell(root, cell, seed, seconds, trace, t0=t0)
    except BenchError as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(finite(out), allow_nan=False), flush=True)
    return 0
