"""The reduction to the program's spans and scopes, on made-up events, on
compiled HLO text and on a small trace recorded on the chip, and the
per-layer readers that read it.

``data/tiny-tpu.xplane.pb`` (and its ``.scopes.json``) is made by
``record_tpu_trace.py``: a 16-host, two-window ``DigitalTwin`` replay.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness, spans

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "chipbench", "tests", "data")
MS = 1_000_000

#: the readers of the program's spans, scopes and counters
READERS = ("scan_device_ms.whatif", "expand_device_ms.whatif",
           "power_device_ms.whatif", "calib_device_ms.twin",
           "inputs_ms.twin", "dispatch_ms.twin", "wait_ms.twin",
           "readback_ms.twin", "replay_fixed_ms.twin", "host_reads.twin")


class E:
    def __init__(self, name, start, end):
        self.name, self.start_ns, self.end_ns = name, start, end


class L:
    def __init__(self, name, events):
        self.name, self.events = name, events


class P:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class PD:
    def __init__(self, planes):
        self.planes = planes


SCOPES = {"jit_step": {"while.1": "opendt.des_expand",
                       "fusion.2": "opendt.des_scan"}}


def made_up():
    """One program run: a while op (expand) holding a fusion (scan) and an
    unscoped copy, then a second run after an idle gap; host spans of two
    windows, one holding a nested span."""
    dev = P("/device:TPU:0", [
        L("XLA Modules", [E("jit_step(7)", 0, 10 * MS),
                          E("jit_step(7)", 20 * MS, 24 * MS)]),
        L("XLA Ops", [
            E("%while.1 = (s32[], f32[4]) while(...)", 0, 10 * MS),
            E("%fusion.2 = f32[4] fusion(...)", 2 * MS, 6 * MS),
            E("%copy.3 = f32[4] copy(...)", 6 * MS, 7 * MS),
            E("%fusion.2 = f32[4] fusion(...)", 20 * MS, 24 * MS)]),
    ])
    host = P("/host:CPU", [L("python3", [
        E("opendt.window", 0, 12 * MS),
        E("opendt.window.step", 0, 11 * MS),
        E("PjitFunction(step)", 1 * MS, 2 * MS),
        E("opendt.window", 12 * MS, 30 * MS),
        E("opendt.window.readback", 13 * MS, 19 * MS),
        E("np.asarray(jax.Array)", 14 * MS, 18 * MS)])])
    return PD([dev, host])


def test_self_times_count_a_covered_child_once():
    got = dict(spans.self_times([(0, 10, "parent"), (2, 6, "child"),
                                 (4, 5, "grandchild"), (6, 7, "second"),
                                 (12, 13, "after")]))
    assert got == {"parent": 5, "child": 3, "grandchild": 1, "second": 1,
                   "after": 1}


def test_device_scope_self_seconds():
    sp = spans.reduce_profile(made_up(), SCOPES)
    assert sp.scope_s == {
        "opendt.des_expand": pytest.approx(0.005),
        "opendt.des_scan": pytest.approx(0.008),
        spans.UNSCOPED: pytest.approx(0.001)}
    # the scopes' self times add up to the busy time, counted once
    assert sum(sp.scope_s.values()) == pytest.approx(0.014)
    # without the programs' scopes every operation is unscoped
    assert set(spans.reduce_profile(made_up(), {}).scope_s) == {
        spans.UNSCOPED}


def test_host_span_self_seconds_and_counts():
    sp = spans.reduce_profile(made_up(), SCOPES)
    assert sp.span_n == {"opendt.window": 2, "opendt.window.step": 1,
                         "opendt.window.readback": 1}
    assert sp.span_s == {"opendt.window": pytest.approx(0.013),
                         "opendt.window.step": pytest.approx(0.011),
                         "opendt.window.readback": pytest.approx(0.006)}


def test_idle_gaps_by_span():
    sp = spans.reduce_profile(made_up(), SCOPES)
    # the 10 ms gap's middle (15 ms) lies in opendt.window.readback, inside
    # opendt.window; the runtime's own event there does not name it
    assert sp.gaps == [("opendt.window.readback", pytest.approx(0.010))]
    assert sp.breakdown() == {"idle_gaps_by_span": [
        ["opendt.window.readback", pytest.approx(0.010)]]}
    assert spans.gaps_by_span([[0, 2], [5, 6]], []) == [
        (spans.OUTSIDE, pytest.approx(3e-9))]


def test_hlo_scopes_of_a_compiled_program():
    def f(x):
        with jax.named_scope("opendt.des_scan"):
            x = jax.lax.fori_loop(0, 3, lambda i, c: jnp.sin(c) * 2.0, x)
        with jax.named_scope("opendt.des_expand"):
            return jnp.cos(x).sum()

    text = jax.jit(f).lower(jnp.ones(8)).compile().as_text()
    got = spans.hlo_scopes(text)
    assert set(got.values()) == {"opendt.des_scan", "opendt.des_expand"}
    whiles = [k for k in got if k.startswith("while")]
    assert whiles and all(got[k] == "opendt.des_scan" for k in whiles)


def test_hlo_scopes_follow_called_computations():
    text = """HloModule m

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %sine = f32[4]{0} sine(%p), metadata={op_name="jit(f)/opendt.power_readout/sin"}
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  ROOT %fusion.7 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation
}
"""
    assert spans.hlo_scopes(text) == {"sine": "opendt.power_readout",
                                      "fusion.7": "opendt.power_readout"}


def test_hlo_scopes_give_a_loop_body_the_loop_scope():
    """A copy the compiler put in a while loop's body, with no op_name of
    its own, is the loop's work."""
    text = """HloModule m

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %copy.3 = f32[4]{0} copy(%g)
  ROOT %t = (s32[], f32[4]{0}) tuple(%i, %copy.3), metadata={op_name="jit(f)/opendt.des_expand/while/body/x"}
}

ENTRY %main (a: (s32[], f32[4])) -> (s32[], f32[4]) {
  %a = (s32[], f32[4]{0}) parameter(0)
  ROOT %while.9 = (s32[], f32[4]{0}) while(%a), condition=%cond, body=%body, metadata={op_name="jit(f)/opendt.des_scan/while"}
}
"""
    got = spans.hlo_scopes(text)
    assert got["while.9"] == "opendt.des_scan"
    assert got["copy.3"] == got["p"] == "opendt.des_scan"
    assert got["t"] == "opendt.des_expand"
    assert spans.instruction("%fusion.7 = f32[4]{0} fusion(%a)") == \
        "fusion.7"


def test_compiled_scopes_collects_each_program():
    def g(x):
        with jax.named_scope("opendt.twin_predict"):
            return jnp.tanh(x) + 1.0

    with spans.compiled_scopes() as found:
        jax.jit(g)(jnp.ones(5)).block_until_ready()
    assert "opendt.twin_predict" in set(found["jit_g"].values())


def run_with(trace=None, counters=None, requests=2):
    cell = harness.Cell(name="c", chips=1, config={}, traffic={},
                        driver=None, end_to_end=[], per_layer=[])
    return harness.Run(cell=cell, device_kind="TPU v5 lite", window={},
                       spans={}, counters=counters or {}, program_spans={},
                       trace=trace, trace_requests=requests)


def reader(name):
    return harness.load_module(
        os.path.join(ROOT, "chipbench", "metrics", name + ".py"),
        "spans_reader_" + name.replace(".", "_"))


class Reduced:
    """The harness's trace reduction, without and with ``spans``."""

    def __init__(self, sp=None):
        if sp is not None:
            self.spans = sp


@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_without_its_spans(name):
    read = reader(name).read
    assert read(run_with()) is None
    assert read(run_with(Reduced())) is None
    empty = spans.Spans(scope_s={}, span_s={}, span_n={}, gaps=[])
    assert read(run_with(Reduced(empty))) is None


def test_readers_read_per_request_and_per_window():
    sp = spans.Spans(
        scope_s={"opendt.des_scan": 2.0, "opendt.des_expand": 6.0,
                 "opendt.power_readout": 0.5,
                 "opendt.twin_calibrate": 0.112},
        span_s={"opendt.ingest": 0.0112, "opendt.window.inputs": 0.0336,
                "opendt.window.step": 0.112, "opendt.window.wait": 0.224,
                "opendt.window.readback": 0.336, "opendt.twin.init": 0.02,
                "opendt.des": 0.01, "opendt.twin.finish": 0.03},
        span_n={"opendt.window": 112, "opendt.ingest": 112,
                "opendt.window.inputs": 112, "opendt.window.step": 112,
                "opendt.window.wait": 112, "opendt.window.readback": 112,
                "opendt.twin.init": 2, "opendt.des": 2,
                "opendt.twin.finish": 2},
        gaps=[])
    run = run_with(Reduced(sp), counters={"host_reads": 3.0})
    got = {name: reader(name).read(run) for name in READERS}
    assert got == {
        "scan_device_ms.whatif": pytest.approx(1000.0),
        "expand_device_ms.whatif": pytest.approx(3000.0),
        "power_device_ms.whatif": pytest.approx(250.0),
        "calib_device_ms.twin": pytest.approx(1.0),
        "inputs_ms.twin": pytest.approx(0.4),
        "dispatch_ms.twin": pytest.approx(1.0),
        "wait_ms.twin": pytest.approx(2.0),
        "readback_ms.twin": pytest.approx(3.0),
        "replay_fixed_ms.twin": pytest.approx(30.0),
        "host_reads.twin": 3.0}


def test_recorded_tpu_trace_has_named_scopes():
    """The scopes of a trace recorded on one TPU v5e chip."""
    path = os.path.join(DATA, "tiny-tpu.xplane.pb")
    with open(path + ".scopes.json") as f:
        scopes = json.load(f)
    sp = spans.reduce_file(path, scopes)
    for scope in ("opendt.des_scan", "opendt.des_expand",
                  "opendt.twin_predict", "opendt.twin_calibrate"):
        assert sp.scope_s.get(scope, 0) > 0, scope
    assert sp.span_n["opendt.window"] == 2
    assert sp.span_n["opendt.window.step"] == 2
    assert sp.span_n["opendt.des"] == 1
    assert all(n.startswith("opendt.") or n == spans.OUTSIDE
               for n, _ in sp.gaps)


def test_probe_reports_the_host_spans_of_a_small_replay():
    """The probe's whole path on the CPU: host spans and the read counter
    are reported; the device scopes, which need a device plane, are not."""
    bench = harness.load_json(os.path.join(DATA, "BENCHMARK.tiny.json"))
    caching = jax.config.jax_enable_compilation_cache
    try:
        out = spans.probe(ROOT, "tiny-replay", 2 ** 31 + 77, 0.5,
                          require_chip=False, bench=bench, traffic_dir=DATA)
    finally:
        jax.config.update("jax_enable_compilation_cache", caching)
    m = out["metrics"]
    for name in ("inputs_ms.twin", "dispatch_ms.twin", "wait_ms.twin",
                 "readback_ms.twin", "replay_fixed_ms.twin"):
        assert m[name] > 0, name
    assert m["host_reads.twin"] == 3.0
    assert "calib_device_ms.twin" not in m
    assert out["span_n"]["opendt.window"] == 8 * out["trace_requests"]
