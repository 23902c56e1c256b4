"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

* busy time: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), averaged
  over the devices traced;
* device time per jitted program: the ``XLA Modules`` events, by name;
* the breakdown: the operations that took most device time, and the idle
  time between device intervals, each gap named by what the host was doing
  at its middle (the innermost ``bench.*`` annotation of the benchmark, or
  else the innermost host event).

Reads the trace with ``jax.profiler.ProfileData`` alone.
"""

from __future__ import annotations

import dataclasses
import glob
import heapq
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
TOP = 10


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    module_s: dict          # program name -> device seconds (all devices)
    devices: int            # device planes in the trace (0: none traced)
    ops: list               # [(op name, device seconds)], most first
    gaps: list              # [(host activity, idle seconds)], most first

    def module_time(self, *parts: str) -> "float | None":
        """Device seconds of the programs whose name holds any of ``parts``;
        ``None`` when none ran in the traced window."""
        hit = [s for name, s in self.module_s.items()
               if any(p in name for p in parts)]
        return sum(hit) / self.devices if hit and self.devices else None

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.ops[:TOP]],
                "idle_gaps": [[n, s] for n, s in self.gaps[:TOP]]}


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _program(name: str) -> str:
    """``jit_twin_step(12)`` -> ``jit_twin_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce_profile(pd, window_s: float) -> Reduced:
    busy_ns, ops, modules, devices = 0, {}, {}, 0
    dev_intervals = []
    host_events = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices += 1
            for line in plane.lines:
                if line.name == OPS_LINE:
                    iv = []
                    for e in line.events:
                        iv.append((e.start_ns, e.end_ns))
                        ops[e.name] = ops.get(e.name, 0) + e.duration_ns
                    merged = _merge(iv)
                    busy_ns += sum(e - s for s, e in merged)
                    dev_intervals.extend(merged)
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        k = _program(e.name)
                        modules[k] = modules.get(k, 0) + e.duration_ns
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    host_events.append((e.start_ns, e.end_ns, e.name))
    gaps = _name_gaps(_merge(dev_intervals), host_events)
    return Reduced(
        window_s=float(window_s), busy_s=busy_ns / max(devices, 1) * 1e-9,
        module_s={k: v * 1e-9 for k, v in modules.items()}, devices=devices,
        ops=sorted(((k, v * 1e-9) for k, v in ops.items()),
                   key=lambda kv: -kv[1]),
        gaps=gaps)


def _name_gaps(busy, host_events) -> list:
    """Idle seconds between device intervals, summed by host activity.

    One sweep over the gaps' middles and the host events in time order; the
    events open at a middle are those still on the heap (keyed by end).
    """
    host_events.sort()
    gaps = sorted(((e0 + s1) // 2, s1 - e0)
                  for (_, e0), (s1, _) in zip(busy, busy[1:]) if s1 > e0)
    by_name: dict = {}
    open_events: list = []
    i = 0
    for mid, length in gaps:
        while i < len(host_events) and host_events[i][0] <= mid:
            s, e, name = host_events[i]
            heapq.heappush(open_events, (e, s, name))
            i += 1
        while open_events and open_events[0][0] < mid:
            heapq.heappop(open_events)
        bench = [(e - s, n) for e, s, n in open_events
                 if n.startswith("bench.")]
        inner = bench or [(e - s, n) for e, s, n in open_events]
        label = min(inner)[1] if inner else "no host event"
        by_name[label] = by_name.get(label, 0) + length * 1e-9
    return sorted(by_name.items(), key=lambda kv: -kv[1])


def find_trace(out_dir: str) -> str:
    files = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    return max(files, key=os.path.getmtime)


def reduce_file(path: str, window_s: float) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), window_s)


def reduce_dir(out_dir: str, window_s: float) -> Reduced:
    return reduce_file(find_trace(out_dir), window_s)
