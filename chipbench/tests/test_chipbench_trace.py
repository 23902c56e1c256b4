"""The trace reduction, on made-up events and on small recorded traces.

The recorded traces are made by ``record_trace.py``: three requests of a
small jitted program, each in a ``bench.request`` annotation, with a 2 ms
``bench.pause`` on the host after each.
"""

import os

import pytest

from chipbench import trace
from chipbench.peaks import peak

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


class E:
    def __init__(self, name, start, end):
        self.name, self.start_ns, self.end_ns = name, start, end
        self.duration_ns = end - start


class L:
    def __init__(self, name, events):
        self.name, self.events = name, events


class P:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class PD:
    def __init__(self, planes):
        self.planes = planes


def made_up():
    ms = 1_000_000
    dev = P("/device:TPU:0", [
        L("XLA Modules", [E("jit_step(3)", 0, 4 * ms),
                          E("jit_step(3)", 10 * ms, 14 * ms)]),
        L("XLA Ops", [E("fusion.1", 0, 3 * ms), E("fusion.2", 2 * ms, 4 * ms),
                      E("fusion.1", 10 * ms, 14 * ms)]),
    ])
    host = P("/host:CPU", [L("main", [
        E("bench.request", 0, 20 * ms), E("bench.summarize", 4 * ms, 9 * ms),
        E("PjitFunction(step)", 9 * ms, 10 * ms)])])
    return PD([dev, P("#Chip0 Misc", []), host])


def test_busy_modules_and_gaps():
    r = trace.reduce_profile(made_up(), window_s=0.02)
    assert r.busy_s == pytest.approx(0.008)
    assert r.module_time("jit_step") == pytest.approx(0.008)
    assert r.module_time("nothing") is None
    assert r.ops[0] == ("fusion.1", pytest.approx(0.007))
    # the 6 ms gap's middle (7 ms) lies in bench.summarize
    assert r.gaps == [("bench.summarize", pytest.approx(0.006))]
    b = r.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_recorded_cpu_trace_has_no_device():
    """A trace with no device plane (``tiny-cpu.xplane.pb``, the same three
    requests recorded on the CPU) yields no device numbers, so the metric
    readers leave their metrics out rather than report a CPU number."""
    r = trace.reduce_file(os.path.join(DATA, "tiny-cpu.xplane.pb"), 0.05)
    assert r.devices == 0 and r.busy_s == 0
    assert r.module_time("jit_") is None
    assert r.ops == [] and r.gaps == []


def test_peak_table():
    assert peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peak("TPU v99")
