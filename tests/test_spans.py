"""Profiler spans, device scopes and read counters of the twin.

* a traced ``DigitalTwin`` run gives one ``opendt.window`` span per window,
  and its four children (inputs, step, wait, readback) cover it;
* the compiled programs carry the device scopes in their HLO metadata;
* ``WindowRecord.host_reads`` counts every device-to-host read a window
  makes of a ``jax.Array``;
* the streaming service's dispatch, harvest and cache spans show in a trace.
"""

import builtins
import gc
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import orchestrator
from repro.core import scenarios as sc
from repro.core.desim import simulate_utilization
from repro.core.orchestrator import Orchestrator, OrchestratorConfig
from repro.core.power import PowerParams
from repro.core.state import (
    SimSlice,
    TwinConfig,
    init_twin_state,
    make_telemetry,
    twin_step,
)
from repro.core.telemetry import CARBON_INTENSITY_KEY, PRICE_KEY
from repro.core.twin import DigitalTwin, TraceGroundTruth
from repro.serve import ServeConfig, SyntheticProducer, TwinService
from repro.traces.carbon import make_diurnal_carbon
from repro.traces.price import make_diurnal_price
from repro.traces.schema import DatacenterConfig
from repro.traces.surf import BINS_PER_DAY, SurfTraceSpec, make_surf22_like

DC = DatacenterConfig(num_hosts=16, cores_per_host=16)
DAYS = 0.5
T_BINS = int(DAYS * BINS_PER_DAY)
CFG = OrchestratorConfig(bins_per_window=36)     # 4 windows
WINDOW_CHILDREN = ("opendt.window.inputs", "opendt.window.step",
                   "opendt.window.wait", "opendt.window.readback")


@pytest.fixture(scope="module")
def workload():
    return make_surf22_like(SurfTraceSpec(days=DAYS, seed=7), DC)


@pytest.fixture(scope="module")
def truth(workload):
    return TraceGroundTruth(workload, DC, T_BINS)


def _host_spans(out_dir) -> list[tuple]:
    """``(name, start_ns, end_ns, stats)`` of every ``opendt.*`` host event
    in the trace written under ``out_dir``."""
    path, = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("opendt."):
                    spans.append((e.name, e.start_ns, e.end_ns,
                                  dict(e.stats)))
    return spans


def _covered(intervals) -> float:
    total, end = 0.0, -np.inf
    for s, e in sorted(intervals):
        s = max(s, end)
        if e > s:
            total += e - s
            end = e
    return total


def test_window_spans_cover_each_window(tmp_path, workload, truth):
    DigitalTwin(workload, DC, T_BINS, CFG).run(truth.window)  # compile
    # a collection pause between two spans of a millisecond window on the
    # CPU would read as uncovered time
    gc.disable()
    try:
        with jax.profiler.trace(str(tmp_path)):
            twin = DigitalTwin(workload, DC, T_BINS, CFG)
            twin.run(truth.window)
    finally:
        gc.enable()
    spans = _host_spans(str(tmp_path))
    tid = twin.orchestrator.trace_id
    names = [n for n, *_ in spans]
    for name, count in (("opendt.twin.init", 1), ("opendt.des", 1),
                        ("opendt.ingest", 4), ("opendt.window", 4),
                        ("opendt.twin.finish", 1)):
        assert names.count(name) == count, name
    windows = {st["window"]: (s, e) for n, s, e, st in spans
               if n == "opendt.window"}
    assert sorted(windows) == [0, 1, 2, 3]
    covered = 0.0
    for w, (s0, e0) in windows.items():
        children = [(n, s, e) for n, s, e, st in spans
                    if n in WINDOW_CHILDREN and st["window"] == w]
        assert sorted(n for n, *_ in children) == sorted(WINDOW_CHILDREN)
        assert all(s0 <= s and e <= e0 for _, s, e in children)
        covered += _covered([(s, e) for _, s, e in children])
    # summed over the windows, so that one scheduling hiccup of the host
    # between two spans of a millisecond window does not decide it
    assert covered >= 0.95 * sum(e - s for s, e in windows.values())
    assert all(st["twin"] == tid for n, s, e, st in spans
               if n != "opendt.twin.init")


def _op_names(compiled_text: str) -> str:
    return " ".join(part.split('"', 1)[0]
                    for part in compiled_text.split('op_name="')[1:])


def test_compiled_programs_carry_the_scopes(workload):
    ss = sc.build_scenario_set(
        workload, DC, [sc.Scenario(name="a"), sc.Scenario(name="b")],
        PowerParams(), max_hosts=DC.num_hosts)
    whatif = _op_names(sc._run_scenarios_jit.lower(
        ss, None, None, None, max_hosts=DC.num_hosts, t_bins=T_BINS,
        max_starts_per_bin=64, model="opendc", use_pallas=False,
        precision="f32").compile().as_text())
    for scope in ("opendt.des_scan", "opendt.des_expand",
                  "opendt.power_readout"):
        assert scope in whatif, scope

    des = _op_names(simulate_utilization.lower(
        workload, num_hosts=DC.num_hosts, cores_per_host=DC.cores_per_host,
        t_bins=T_BINS).compile().as_text())
    assert "opendt.des_scan" in des and "opendt.des_expand" in des

    cfg = TwinConfig(bins_per_window=36, dc=DC)
    u = np.zeros((36, DC.num_hosts), np.float32)
    step = _op_names(jax.jit(twin_step).lower(
        init_twin_state(cfg), make_telemetry(u, u[:, 0]),
        SimSlice(u_th=jnp.asarray(u))).compile().as_text())
    assert "opendt.twin_predict" in step
    assert "opendt.twin_calibrate" in step


class _CountReads:
    """Counts every ``np.asarray``, ``np.array`` and ``float`` of a
    ``jax.Array`` made in the orchestrator's module, wherever it is made."""

    def __init__(self, monkeypatch):
        self.count, self.nbytes = 0, 0
        seen = self._seen

        class Numpy:
            def __getattr__(self, name):
                return getattr(np, name)

            def asarray(self, x, *a, **kw):
                seen(x)
                return np.asarray(x, *a, **kw)

            def array(self, x, *a, **kw):
                seen(x)
                return np.array(x, *a, **kw)

        def as_float(x):
            seen(x)
            return builtins.float(x)

        monkeypatch.setattr(orchestrator, "np", Numpy())
        monkeypatch.setattr(orchestrator, "float", as_float, raising=False)

    def _seen(self, x):
        if isinstance(x, jax.Array):
            self.count += 1
            self.nbytes += x.nbytes


@pytest.mark.parametrize("traces", ["none", "forecast", "measured"])
def test_host_reads_count_every_device_read(monkeypatch, workload, truth,
                                            traces):
    ci = make_diurnal_carbon(T_BINS, seed=4)
    price = make_diurnal_price(T_BINS, seed=5)
    kw = {} if traces == "none" else dict(carbon_intensity=ci, price=price)
    orch = Orchestrator(workload, DC, T_BINS, CFG, **kw)
    reads = _CountReads(monkeypatch)
    for w in range(orch.num_windows):
        tw = truth.window(w, CFG.bins_per_window)
        if traces == "measured":
            sl = orch.window_slice(w)
            tw.extras.update({CARBON_INTENSITY_KEY: ci[sl],
                              PRICE_KEY: price[sl]})
        if w != 2:                       # window 2: telemetry not landed
            orch.store.ingest(tw)
        before = (reads.count, reads.nbytes)
        rec = orch.run_window(w)
        assert rec.host_reads == reads.count - before[0]
        assert rec.host_read_bytes == reads.nbytes - before[1]
    per_window = [r.host_reads for r in orch.records]
    # mape, queue and power when telemetry landed; gCO2 and cost with traces
    extra = {"none": 0, "forecast": 2, "measured": 2}[traces]
    assert per_window == [3 + extra, 3 + extra, extra, 3 + extra]


def test_service_spans_in_a_drain(tmp_path):
    twin = TwinConfig(bins_per_window=6,
                      dc=DatacenterConfig(num_hosts=4, cores_per_host=4))
    svc = TwinService(ServeConfig(twin=twin, lanes=4))
    for i in range(4):
        svc.admit(f"t{i}")
        svc.attach(SyntheticProducer(f"t{i}", hosts=4, bins_per_window=6,
                                     num_windows=3, seed=i))
    with jax.profiler.trace(str(tmp_path)):
        results = svc.run_until_idle()
    assert len(results) == 12
    names = [n for n, *_ in _host_spans(str(tmp_path))]
    assert names.count("opendt.serve.dispatch") == svc.stats.batches
    assert names.count("opendt.serve.harvest") == svc.stats.batches
    assert names.count("opendt.serve.cache_put") == 12
