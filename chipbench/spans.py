"""Reduction of a profiler trace to the program's own spans and scopes.

* device self seconds by scope: each ``XLA Ops`` event's duration less the
  part covered by the events nested in it on the same line, summed by the
  innermost ``opendt.*`` scope (``jax.named_scope``) in the operation's
  HLO ``op_name``.  A TPU trace names an operation by its HLO instruction
  (``%while.49 = ...``) and keeps no ``op_name``, so the scope of each
  instruction is read from the compiled programs' HLO text, collected
  while they compile (:func:`compiled_scopes`), and each operation is
  placed in the program (``XLA Modules`` event) that encloses it;
* host self seconds and counts by span: each ``opendt.*`` host event
  (``jax.profiler.TraceAnnotation``) less the ``opendt.*`` events nested in
  it on its thread;
* idle gaps by span: the idle time between device intervals, each gap
  named by the innermost ``opendt.*`` span open at its middle, or
  :data:`OUTSIDE`.

Reads the trace with ``jax.profiler.ProfileData`` alone.  The per-layer
readers under ``metrics/`` read a :class:`Spans` from ``run.trace.spans``.

    python3 -m chipbench.spans --workload <cell> --seed <n> --seconds <s>

runs one cell's window traced, as ``run.py --trace 1`` does, and prints the
metrics of the readers named after the cell's per-layer metrics (``*.twin``
for a cell whose metrics end in ``.twin``) with the reductions above, and
each request's wall time inside and outside the profiler session.  The
last line of standard output is one JSON object.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import heapq
import re

from chipbench.trace import (
    DEVICE_PLANE,
    HOST_PLANE,
    MODULES_LINE,
    OPS_LINE,
    _merge,
    _program,
)

PREFIX = "opendt."
SCOPE = re.compile(r"opendt\.[A-Za-z0-9_]+(?:\.[A-Za-z0-9_]+)*")
OUTSIDE = "outside program spans"
UNSCOPED = "(no scope)"
TOP = 10


@dataclasses.dataclass
class Spans:
    scope_s: dict       # device scope -> self seconds (all devices)
    span_s: dict        # host span name -> self seconds
    span_n: dict        # host span name -> count
    gaps: list          # [(innermost span at the gap, idle seconds)]
    #: [(program/instruction, self seconds)] of unscoped operations
    unscoped: list = dataclasses.field(default_factory=list)

    def breakdown(self) -> dict:
        return {"idle_gaps_by_span": [[n, s] for n, s in self.gaps[:TOP]]}


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = ")
_COMP = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+)\s.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"(?:calls|body|condition|to_apply)=%([^\s,}]+)")


def hlo_scopes(text: str) -> dict:
    """``{instruction: scope}`` of one compiled HLO module's text.

    An instruction's scope is the innermost ``opendt.*`` scope of its
    ``op_name``; where it has none (the compiler's own copies and
    reshapes), that of the instruction that runs its computation (a while
    loop's body belongs to the loop); else the first scope inside a
    computation it calls (a fusion's).  Unscoped ones are left out."""
    comps: dict = {}    # computation -> [(instruction, scope, called)]
    comp = None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        found = SCOPE.findall(op.group(1)) if op else []
        comps.setdefault(comp, []).append(
            (m.group(1), found[-1] if found else None,
             _CALLED.findall(line)))
    outer: dict = {}    # computation -> scope of the instruction running it
    grew = True
    while grew:
        grew = False
        for c, instrs in comps.items():
            for _, own, called in instrs:
                s = own or outer.get(c)
                for k in called if s else ():
                    if k not in outer:
                        outer[k], grew = s, True
    inner = {c: next((s for _, s, _ in instrs if s), None)
             for c, instrs in comps.items()}
    scope = {}
    for c, instrs in comps.items():
        for name, own, called in instrs:
            s = own or outer.get(c) or next(
                (inner[k] for k in called if inner.get(k)), None)
            if s:
                scope[name] = s
    return scope


@contextlib.contextmanager
def compiled_scopes():
    """Collects ``{program: {instruction: scope}}`` of every program
    compiled, or loaded from the persistent cache, inside the block."""
    from jax._src import compiler

    real = compiler.compile_or_get_cached
    found: dict = {}

    def collect(*args, **kwargs):
        exe = real(*args, **kwargs)
        for module in exe.hlo_modules():
            found.setdefault(module.name, {}).update(
                hlo_scopes(module.to_string()))
        return exe

    compiler.compile_or_get_cached = collect
    try:
        yield found
    finally:
        compiler.compile_or_get_cached = real


def instruction(event_name: str) -> str:
    """``%while.49 = (s32[], ...`` -> ``while.49``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def self_times(events):
    """``(event, self ns)`` for events of one line, each less the union of
    the events directly nested in it."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    out = []
    stack = []      # [start, end, covered up to, covered ns, item]

    def close(top):
        s, e, _, cov, item = top
        out.append((item, e - s - cov))
    for s, e, item in events:
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            parent = stack[-1]
            lo, hi = max(s, parent[2]), min(e, parent[1])
            if hi > lo:
                parent[3] += hi - lo
            parent[2] = max(parent[2], hi)
        stack.append([s, e, s, 0, item])
    while stack:
        close(stack.pop())
    return out


def reduce_profile(pd, scopes: dict) -> Spans:
    """``scopes`` is :func:`compiled_scopes`' ``{program: {instruction:
    scope}}``."""
    scope_ns: dict = {}
    unscoped: dict = {}
    span_ns: dict = {}
    span_n: dict = {}
    busy = []
    host = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            programs = sorted((e.start_ns, e.end_ns, _program(e.name))
                              for e in (lines[MODULES_LINE].events
                                        if MODULES_LINE in lines else ()))
            starts = [p[0] for p in programs]
            evs = [(e.start_ns, e.end_ns, e.name)
                   for e in lines[OPS_LINE].events]
            busy.extend((s, e) for s, e, _ in evs)
            for (name, start), ns in self_times(
                    [(s, e, (n, s)) for s, e, n in evs]):
                k = bisect.bisect_right(starts, start) - 1
                program = programs[k][2] \
                    if k >= 0 and start < programs[k][1] else "?"
                instr = instruction(name)
                scope = scopes.get(program, {}).get(instr, UNSCOPED)
                scope_ns[scope] = scope_ns.get(scope, 0) + ns
                if scope == UNSCOPED:
                    key = program + "/" + instr
                    unscoped[key] = unscoped.get(key, 0) + ns
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = [(e.start_ns, e.end_ns, e.name) for e in line.events
                       if e.name.startswith(PREFIX)]
                host.extend(evs)
                for name, ns in self_times(evs):
                    span_ns[name] = span_ns.get(name, 0) + ns
                    span_n[name] = span_n.get(name, 0) + 1
    return Spans(scope_s={k: v * 1e-9 for k, v in scope_ns.items()},
                 span_s={k: v * 1e-9 for k, v in span_ns.items()},
                 span_n=span_n, gaps=gaps_by_span(_merge(busy), host),
                 unscoped=sorted(((k, v * 1e-9) for k, v in unscoped.items()),
                                 key=lambda kv: -kv[1])[:TOP])


def gaps_by_span(busy, spans) -> list:
    """Idle seconds between device intervals, summed by the innermost
    program span open at each gap's middle (the shortest one open)."""
    spans = sorted(spans)
    gaps = sorted(((e0 + s1) // 2, s1 - e0)
                  for (_, e0), (s1, _) in zip(busy, busy[1:]) if s1 > e0)
    by_name: dict = {}
    open_spans: list = []
    i = 0
    for mid, length in gaps:
        while i < len(spans) and spans[i][0] <= mid:
            s, e, name = spans[i]
            heapq.heappush(open_spans, (e, s, name))
            i += 1
        while open_spans and open_spans[0][0] < mid:
            heapq.heappop(open_spans)
        inner = [(e - s, n) for e, s, n in open_spans]
        label = min(inner)[1] if inner else OUTSIDE
        by_name[label] = by_name.get(label, 0) + length * 1e-9
    return sorted(by_name.items(), key=lambda kv: -kv[1])


def reduce_file(path: str, scopes: dict) -> Spans:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), scopes)


# -- what the readers under metrics/ share ------------------------------------

def of(run) -> "Spans | None":
    return getattr(run.trace, "spans", None) if run.trace is not None \
        else None


def scope_ms(run, scope: str, per: str = "request") -> "float | None":
    """Device self ms of ``scope`` per request, or per ``opendt.window``
    span with ``per="window"``."""
    sp = of(run)
    if sp is None or sp.scope_s.get(scope, 0) <= 0:
        return None
    n = count(run, per)
    return None if not n else sp.scope_s[scope] / n * 1e3


def span_ms(run, names, per: str = "window") -> "float | None":
    """Host self ms of the named spans per ``opendt.window`` span, or per
    request with ``per="request"``; ``None`` when none of them ran."""
    sp = of(run)
    if sp is None or not any(sp.span_n.get(k) for k in names):
        return None
    n = count(run, per)
    return None if not n else sum(sp.span_s.get(k, 0.0)
                                  for k in names) / n * 1e3


def count(run, per: str) -> int:
    if per == "request":
        return run.trace_requests
    sp = of(run)
    return sp.span_n.get(PREFIX + "window", 0) if sp is not None else 0


# -- a traced window of one cell, with these readers --------------------------

def _request_ends(state) -> list:
    recs = getattr(state, "replays", None) or getattr(state, "batches", [])
    return [r.get("t1", r.get("t3")) for r in recs]


def probe(root, cell_name, seed, seconds, *, require_chip=True, bench=None,
          traffic_dir=None) -> dict:
    """One traced window of ``cell_name``, as ``run.py --trace 1`` runs it
    (no check of ``correct``), reduced with this module's readers."""
    import os
    import shutil

    import jax

    from chipbench import harness
    from chipbench import trace as trace_mod

    cell = harness.resolve(root, cell_name, bench, traffic_dir)
    device = harness.device_info(cell.chips, require_chip)
    # compile every program afresh: an executable read back from the
    # persistent cache gave no scopes for twin_step on the chip
    jax.config.update("jax_enable_compilation_cache", False)
    out_dir = os.path.join(root, ".chipbench_trace", "spans-" + cell_name)
    tracer = harness.Tracer(True, out_dir)
    drv = cell.driver
    with compiled_scopes() as scopes:
        state = drv.setup(cell, seed, seconds)
    window = drv.window(state, seconds, tracer)
    ends = _request_ends(state)
    reduced = trace_mod.reduce_dir(out_dir, tracer.t_stop - tracer.t_start)
    reduced.spans = reduce_file(trace_mod.find_trace(out_dir), scopes)
    shutil.rmtree(out_dir, ignore_errors=True)
    counters = dict(window.get("counters", {}))
    replays = getattr(state, "replays", None)
    if replays:
        reads = [r.host_reads for rep in replays
                 for r in rep["res"].records]
        counters["host_reads"] = sum(reads) / len(reads)
    run = harness.Run(cell=cell, device_kind=device["kind"], window=window,
                      spans=window.get("spans", {}), counters=counters,
                      program_spans=window.get("program_spans", {}),
                      trace=reduced, trace_requests=tracer.requests,
                      min_bytes=float(drv.min_bytes(state)))
    suffixes = {m["name"].rsplit(".", 1)[-1] for m in cell.per_layer}
    metrics = {}
    mdir = os.path.join(root, "chipbench", "metrics")
    for f in sorted(os.listdir(mdir)):
        name = f[:-3]
        if f.endswith(".py") and name.rsplit(".", 1)[-1] in suffixes:
            reader = harness.load_module(os.path.join(mdir, f),
                                         "spans_" + name.replace(".", "_"))
            value = reader.read(run)
            if value is not None:
                metrics[name] = value
    # wall time of requests 1, 2, ... from the driver's own end stamps.
    # The driver traces requests 1 to `last - 1`; request 1 (start_trace
    # before it) and request `last` (stop_trace before it) are left out.
    walls = [b - a for a, b in zip(ends, ends[1:])]
    last = 1 + tracer.requests
    sp = reduced.spans
    return {"cell": cell_name, "seed": seed, "device": device,
            "metrics": metrics, "window_s": reduced.window_s,
            "busy_s": reduced.busy_s, "trace_requests": tracer.requests,
            "scope_s": sp.scope_s, "span_s": sp.span_s, "span_n": sp.span_n,
            "idle_gaps_by_span": sp.breakdown()["idle_gaps_by_span"],
            "unscoped": sp.unscoped,
            "wall_traced_s": walls[1:last - 1],
            "wall_untraced_s": walls[last:]}


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    out = probe(root, args.workload, args.seed, args.seconds)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
