"""Smoke run of the twin's main path on a TPU, at the SURF-SARA size.

    python chip_smoke.py               # one chip: loop, DES, what-if, service
    python chip_smoke.py --four-chips  # sharded S and D axes vs one chip

The deployment is the paper's: 277 hosts x 16 cores, a 7-day horizon in
5-minute bins (T=2016), from the seeded ``make_surf22_like`` trace.  One
process drives the chip(s).  Every phase prints its checks and its cold
and warm wall times on its own lines; a failed check raises, so the script
exits non-zero.  Only when every phase passed is the last line of standard
output the verdict

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Without a TPU, or outside a checkout of this repository, it exits non-zero
and prints no verdict.  This is a smoke run, not a benchmark: its times
include host work and are printed for orientation only.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
DAYS = 7.0

#: f32 engine vs reference tolerances of tests/test_oracle.py, per float leaf
#: (rtol, atol); derived leaves (tflops, efficiency, cost) take the looser one
ORACLE_TOL = {
    "u_th": (2e-5, 1e-6),
    "utilization": (1e-4, 1e-6),
    "power_w": (1e-4, 0.0),
    "power_demand_w": (1e-4, 0.0),
    "energy_kwh": (1e-4, 0.0),
    "pue": (1e-4, 0.0),
    "gco2": (2e-4, 0.0),
    "energy_cost": (2e-4, 0.0),
    "tflops": (2e-4, 1e-6),
    "efficiency": (2e-4, 1e-6),
}
#: float leaves the oracle does not cover (MAPEs, power-model parameters)
DEFAULT_TOL = (1e-4, 1e-6)


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def timed(fn):
    """``(result, seconds)`` with the result ready on the device."""
    import jax

    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def named_leaves(tree, prefix: str = "") -> dict:
    """``{path: array}`` through tuples and dataclasses (``0.u_th``,
    ``1.params_used.r``); ``None`` leaves and static config drop out."""
    import jax
    import numpy as np

    if isinstance(tree, (tuple, list)):
        parts = [(f"{prefix}{i}.", x) for i, x in enumerate(tree)]
    elif dataclasses.is_dataclass(tree):
        parts = [(f"{prefix}{f.name}.", getattr(tree, f.name))
                 for f in dataclasses.fields(tree)]
    else:
        is_array = isinstance(tree, (jax.Array, np.ndarray))
        return {prefix[:-1]: tree} if is_array else {}
    out = {}
    for p, x in parts:
        out.update(named_leaves(x, p))
    return out


def compare(got, want, what: str) -> dict:
    """Integer and bool leaves exactly, float leaves within their oracle
    tolerance.  Returns ``{leaf: max |diff|}`` for the float leaves that
    are not bitwise equal."""
    import numpy as np

    g, w = named_leaves(got), named_leaves(want)
    check(g.keys() == w.keys(), f"{what}: leaves differ {g.keys() ^ w.keys()}")
    off = {}
    for name in w:
        a, b = np.asarray(g[name]), np.asarray(w[name])
        check(a.shape == b.shape, f"{what}: {name} shape {a.shape} != {b.shape}")
        if not np.issubdtype(b.dtype, np.floating):
            check(np.array_equal(a, b),
                  f"{what}: {name} differs at {np.argwhere(a != b)[:5].tolist()}")
            continue
        if np.array_equal(a, b, equal_nan=True):
            continue
        off[name] = float(np.nanmax(np.abs(a.astype(np.float64) - b)))
        rtol, atol = ORACLE_TOL.get(name.rsplit(".", 1)[-1], DEFAULT_TOL)
        check(np.allclose(a, b, rtol=rtol, atol=atol, equal_nan=True),
              f"{what}: {name} outside rtol={rtol} atol={atol}: max |diff| "
              f"{off[name]:.3g}")
    return off


def bitwise_note(off: dict) -> str:
    """``yes``, or the float leaves that differ and their max |diff|."""
    if not off:
        return "yes"
    return "no: " + ", ".join(f"{k} {v:.3g}" for k, v in sorted(off.items()))


def trees_bitwise(a, b) -> bool:
    import jax
    import numpy as np

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
        for x, y in zip(la, lb))


# -- shared inputs ------------------------------------------------------------

def surf(days: float = DAYS, hosts: int | None = None):
    """The seeded SURF-SARA-like trace: ``(workload, dc, t_bins)``."""
    from repro.traces.schema import DatacenterConfig
    from repro.traces.surf import BINS_PER_DAY, SurfTraceSpec, make_surf22_like

    dc = DatacenterConfig() if hosts is None else DatacenterConfig(
        num_hosts=hosts)
    w = make_surf22_like(SurfTraceSpec(days=days, seed=22), dc)
    return w, dc, int(days * BINS_PER_DAY)


def traces(t_bins: int) -> dict:
    """Carbon, ambient and price traces for the what-if batches."""
    from repro.traces.carbon import make_diurnal_carbon
    from repro.traces.price import make_diurnal_price
    from repro.traces.thermal import make_diurnal_ambient

    return dict(carbon_intensity=make_diurnal_carbon(t_bins, seed=1),
                ambient_c=make_diurnal_ambient(t_bins, seed=2),
                price=make_diurnal_price(t_bins, seed=3))


def whatif_grid(s: int, hosts: int) -> list:
    """``s`` scenarios cycling failure x PUE x cap x scheduler lanes."""
    from repro.core.scenarios import Scenario
    from repro.runtime.fault import DEGRADED, OUTAGE, HostFailure

    fail_sets = ((), (HostFailure(host=4, start_bin=20, end_bin=400,
                                  kind=OUTAGE),
                      HostFailure(host=hosts // 2, start_bin=60,
                                  end_bin=900, kind=DEGRADED)))
    pues = ((1.0, 0.0, 0.0), (1.12, 0.08, 0.004))
    caps = (None, 45_000.0, 60_000.0, 75_000.0)
    scheds = (("worst_fit", 0), ("best_fit", 0), ("first_fit", 0),
              ("best_fit", 2))
    out = []
    for i in range(s):
        policy, depth = scheds[i % 4]
        fails = fail_sets[(i // 4) % 2]
        base, load, amb = pues[(i // 8) % 2]
        cap = caps[(i // 16) % 4]
        out.append(Scenario(
            name=f"s{i}", failures=fails, pue_base=base, pue_load_coeff=load,
            pue_amb_coeff=amb, power_cap_w=cap, policy=policy,
            backfill_depth=depth))
    return out


# -- phase 1 ------------------------------------------------------------------

def device_info(n_chips: int) -> dict:
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        print(f"FAIL: JAX found no TPU (first device: {d0.platform}); this "
              "script never falls back to the CPU", file=sys.stderr)
        sys.exit(1)
    log(f"[device] platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)}")
    if len(devs) < n_chips:
        print(f"FAIL: need {n_chips} chips, JAX sees {len(devs)}",
              file=sys.stderr)
        sys.exit(1)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


# -- phase 2 ------------------------------------------------------------------

def phase_closed_loop(backends=("xla", "pallas"), days: float = DAYS) -> None:
    """NFR2 closed loop, calibrated, once per kernel backend."""
    import numpy as np

    from repro.core import OrchestratorConfig, run_surf_experiment

    w, dc, t_bins = surf(days)
    per_window = {}
    for backend in backends:
        cfg = OrchestratorConfig(kernel_backend=backend)

        def run():
            return run_surf_experiment(w, dc, t_bins, calibrate=True, cfg=cfg)

        cold, t_cold = timed(run)
        warm, t_warm = timed(run)
        check(np.array_equal(cold.per_window_mape, warm.per_window_mape,
                             equal_nan=True),
              f"closed loop [{backend}]: warm run differs from cold run")
        check(np.isfinite(warm.overall_mape),
              f"closed loop [{backend}]: overall MAPE {warm.overall_mape}")
        per_window[backend] = warm.per_window_mape
        log(f"[closed-loop {backend}] {days:g} days, {t_bins} bins, "
            f"{len(warm.records)} windows, {int(w.num_jobs)} jobs: "
            f"cold {t_cold:.2f} s, warm {t_warm:.2f} s, "
            f"compile ~{t_cold - t_warm:.2f} s; "
            f"overall MAPE {warm.overall_mape:.4f} %")
    ref, *others = backends
    for b in others:
        diff = np.nanmax(np.abs(per_window[b] - per_window[ref]))
        check(np.allclose(per_window[b], per_window[ref], rtol=0.0,
                          atol=1e-3, equal_nan=True),
              f"closed loop: {b} vs {ref} per-window MAPE max |diff| {diff}")
        log(f"[closed-loop] {b} == {ref} per-window MAPE within atol 1e-3 "
            f"(max |diff| {diff:.3g})")


# -- phase 3 ------------------------------------------------------------------

def _des_scenarios(hosts: int) -> list:
    from repro.core.scenarios import Scenario
    from repro.runtime.fault import OUTAGE, HostFailure

    return [
        Scenario(name="worst-fit"),
        Scenario(name="best-fit-bf2", policy="best_fit", backfill_depth=2),
        Scenario(name="first-fit", policy="first_fit"),
        Scenario(name="random-fit", policy="random_fit"),
        Scenario(name="outage-cap", power_cap_w=60_000.0, failures=(
            HostFailure(host=hosts - 1, start_bin=30, end_bin=500,
                        kind=OUTAGE),)),
    ]


def phase_des_vs_cpu(days: float = DAYS) -> None:
    """Masked DES + readout on the chip vs the same program on the CPU."""
    import jax

    from repro.core.scenarios import build_scenario_set, run_scenarios

    w, dc, t_bins = surf(days)
    scs = _des_scenarios(dc.num_hosts)

    def run():
        ss = build_scenario_set(w, dc, scs)
        return run_scenarios(ss, max_hosts=ss.max_hosts, t_bins=t_bins,
                             carbon_intensity=traces(t_bins)["carbon_intensity"])

    cpu = jax.devices("cpu")[0]
    chip, t_cold = timed(run)
    _, t_warm = timed(run)
    with jax.default_device(cpu):
        host, t_cpu = timed(run)
    off = compare(chip, host, "des chip vs cpu")
    log(f"[des] S={len(scs)} lanes x {dc.num_hosts} hosts x {t_bins} bins, "
        f"{int(w.num_jobs)} jobs: chip == CPU schedules exact, floats within "
        f"oracle tolerance (bitwise: {bitwise_note(off)}); chip cold "
        f"{t_cold:.2f} s, warm {t_warm:.2f} s; CPU cold {t_cpu:.2f} s")


# -- phase 4 ------------------------------------------------------------------

def phase_whatif(s: int = 64, days: float = DAYS) -> None:
    """Mixed what-if batch, unfused readout vs the fused Pallas readout."""
    import numpy as np

    from repro.core import scenarios as sc_mod
    from repro.core.scenarios import build_scenario_set, run_scenarios

    w, dc, t_bins = surf(days)
    ss = build_scenario_set(w, dc, whatif_grid(s, dc.num_hosts))
    tr = traces(t_bins)
    cache = run_scenarios._cache_size
    check(cache is not None, "what-if: jit cache counter unavailable")
    outs = {}
    for use_pallas in (False, True):
        def run():
            return run_scenarios(ss, max_hosts=ss.max_hosts, t_bins=t_bins,
                                 use_pallas=use_pallas, **tr)

        before = cache()
        cold, t_cold = timed(run)
        after_cold = cache()
        warm, t_warm = timed(run)
        check(after_cold - before == 1,
              f"what-if use_pallas={use_pallas}: {after_cold - before} "
              "compiles on the cold call, want 1")
        check(cache() == after_cold,
              f"what-if use_pallas={use_pallas}: the warm call recompiled")
        check(trees_bitwise(cold, warm),
              f"what-if use_pallas={use_pallas}: warm differs from cold")
        outs[use_pallas] = warm
        log(f"[what-if use_pallas={use_pallas}] S={s} x {dc.num_hosts} "
            f"hosts x {t_bins} bins: 1 compile; cold {t_cold:.2f} s, "
            f"warm {t_warm:.2f} s")
    off = compare(outs[True], outs[False], "what-if pallas vs unfused")
    energy = np.asarray(outs[True][1].energy_kwh).sum(axis=1)
    check(np.isfinite(energy).all() and (energy > 0).all(),
          "what-if: non-positive or non-finite lane energy")
    log(f"[what-if] pallas == unfused: schedules exact, floats within oracle "
        f"tolerance (bitwise: {bitwise_note(off)})")
    # the program run_scenarios(use_pallas=True) compiled, lowered again:
    # a Mosaic kernel shows as tpu_custom_call, interpret mode does not
    anon = dataclasses.replace(ss, names=("",) * s)
    hlo = sc_mod._run_scenarios_jit.lower(
        anon, *(np.asarray(tr[k], np.float32) for k in
                ("carbon_intensity", "ambient_c", "price")),
        max_hosts=ss.max_hosts, t_bins=t_bins, max_starts_per_bin=64,
        model="opendc", use_pallas=True, precision="f32").as_text()
    check("tpu_custom_call" in hlo,
          "what-if: use_pallas program has no tpu_custom_call")
    log("[what-if] use_pallas program lowers to tpu_custom_call "
        "(compiled Mosaic kernel, not interpret mode)")


# -- phase 5 ------------------------------------------------------------------

def phase_service(tenants: int = 16, windows: int = 4,
                  hosts: int | None = None) -> None:
    """TwinService with every lane busy: lane-isolated bit for bit, and
    equal to solo twin_step streams."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.state import (SimSlice, TwinConfig, init_twin_state,
                                  make_telemetry, twin_step)
    from repro.serve import ServeConfig, SyntheticProducer, TwinService
    from repro.traces.schema import DatacenterConfig

    dc = DatacenterConfig() if hosts is None else DatacenterConfig(
        num_hosts=hosts)
    twin = TwinConfig(bins_per_window=36, dc=dc)
    streams = {
        f"t{i:02d}": SyntheticProducer(
            f"t{i:02d}", hosts=dc.num_hosts, bins_per_window=36,
            num_windows=windows, seed=i).poll(float("inf"))
        for i in range(tenants)}

    def serve(order, rounds, cache):
        """Admit tenants in ``order``; serve each group of ``rounds``."""
        svc = TwinService(ServeConfig(twin=twin, lanes=tenants, cache=cache,
                                      queue_capacity=tenants * windows))
        for t in order:
            svc.admit(t)
        for group in rounds:
            for w_i in range(windows):
                for t in group:
                    check(svc.submit(streams[t][w_i]),
                          f"service: queue rejected {t}/{w_i}")
            svc.run_until_idle(pump=False)
        results = svc.drain()
        check(len(results) == tenants * windows,
              f"service: {len(results)} results, want {tenants * windows}")
        check(svc.compile_count() == 1,
              f"service: fleet program compiled {svc.compile_count()}x")
        return svc, {(r.tenant, r.window): r.output for r in results}

    names = list(streams)
    t0 = time.perf_counter()
    svc, served = serve(names, [names], cache=True)
    t_serve = time.perf_counter() - t0
    # the same streams on other lanes, in half-empty batches: a tenant's
    # result may not depend on its lane or its batch-mates (the result
    # cache reuses outputs across batches on that premise)
    _, moved = serve(names[::-1], [names[0::2], names[1::2]], cache=False)
    for key, out in served.items():
        check(trees_bitwise(out, moved[key]),
              f"service: {key} changes with its lane or batch-mates")

    solo = jax.jit(twin_step)
    off = {}
    for t, evs in streams.items():
        state = init_twin_state(twin)
        for ev in evs:
            state, out = solo(state, make_telemetry(ev.u_th, ev.power_w),
                              SimSlice(u_th=jnp.asarray(ev.sim_u)))
            for k, v in compare(served[(t, ev.window)],
                                jax.tree.map(np.asarray, out),
                                f"service {t} window {ev.window}").items():
                off[k] = max(v, off.get(k, 0.0))
    log(f"[service] {tenants} tenants x {windows} windows x {dc.num_hosts} "
        f"hosts on {tenants} lanes: 1 compile, {svc.stats.batches} batches "
        f"in {t_serve:.2f} s (incl. compile); lane-isolated bit for bit "
        f"(other lanes, half-empty batches)")
    log(f"[service] == solo twin_step: ints exact, floats within tolerance "
        f"(bitwise: {bitwise_note(off)})")


# -- four chips -----------------------------------------------------------------

def _spread(tree) -> int:
    """Devices the first leaf's shards live on."""
    import jax

    return len(jax.tree.leaves(tree)[0].sharding.device_set)


def phase_sharded_scenarios(n_dev: int, days: float = DAYS,
                            sizes=(64, 4)) -> None:
    """run_scenarios(shard=True) vs the one-chip vmap path."""
    from repro.core.scenarios import build_scenario_set, run_scenarios

    w, dc, t_bins = surf(days)
    tr = traces(t_bins)
    for s in sizes:
        ss = build_scenario_set(w, dc, whatif_grid(s, dc.num_hosts))
        kw = dict(max_hosts=ss.max_hosts, t_bins=t_bins, **tr)
        one, t_one = timed(lambda: run_scenarios(ss, **kw))
        sh, t_cold = timed(lambda: run_scenarios(ss, shard=True, **kw))
        _, t_warm = timed(lambda: run_scenarios(ss, shard=True, **kw))
        off = compare(sh, one, f"sharded S={s}")
        spread = _spread(sh)
        check(spread == n_dev,
              f"sharded S={s}: outputs on {spread} devices, want {n_dev}")
        log(f"[shard-S] S={s} on {n_dev} devices x {dc.num_hosts} hosts x "
            f"{t_bins} bins: sharded == one chip (ints exact, floats "
            f"bitwise: {bitwise_note(off)}); outputs on {spread} devices; "
            f"one chip {t_one:.2f} s, sharded cold {t_cold:.2f} s, warm "
            f"{t_warm:.2f} s")


def phase_sharded_fleet(n_dev: int, sizes=(8, 6, 4), windows: int = 4,
                        hosts: int | None = None) -> None:
    """run_fleet(shard=True) vs run_fleet on one chip, D lanes."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.state import (SimSlice, TelemetrySlice, TwinConfig,
                                  init_twin_state)
    from repro.core.twin import run_fleet, stack_twin_states
    from repro.serve import SyntheticProducer
    from repro.traces.schema import DatacenterConfig

    dc = DatacenterConfig() if hosts is None else DatacenterConfig(
        num_hosts=hosts)
    twin = TwinConfig(bins_per_window=36, dc=dc)
    for d in sizes:
        evs = [SyntheticProducer(f"d{i}", hosts=dc.num_hosts,
                                 bins_per_window=36, num_windows=windows,
                                 seed=100 + i).poll(float("inf"))
               for i in range(d)]
        u = jnp.asarray(np.stack([[e[w].u_th for e in evs]
                                  for w in range(windows)]))
        p = jnp.asarray(np.stack([[e[w].power_w for e in evs]
                                  for w in range(windows)]))
        telem = TelemetrySlice(u_th=u, power_w=p,
                               valid=jnp.ones((windows, d), bool))
        sims = SimSlice(u_th=u)

        def fresh():
            return stack_twin_states([init_twin_state(twin)] * d)

        one, t_one = timed(lambda: run_fleet(fresh(), telem, sims))
        sh, t_cold = timed(lambda: run_fleet(fresh(), telem, sims,
                                             shard=True))
        _, t_warm = timed(lambda: run_fleet(fresh(), telem, sims,
                                            shard=True))
        off = compare(sh, one, f"sharded fleet D={d}")
        spread = _spread(sh)
        check(spread == n_dev,
              f"sharded fleet D={d}: outputs on {spread} devices, want "
              f"{n_dev}")
        log(f"[shard-D] D={d} on {n_dev} devices x "
            f"{dc.num_hosts} hosts x {windows} windows: sharded == one chip "
            f"(ints exact, floats bitwise: {bitwise_note(off)}); outputs on "
            f"{spread} devices; one chip {t_one:.2f} s, sharded cold "
            f"{t_cold:.2f} s, warm {t_warm:.2f} s")


# -- main ---------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded S and D paths on 4 chips, "
                         "each against the one-chip path")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"FAIL: {ROOT} holds no checkout of the repository (src/repro "
              "is missing)", file=sys.stderr)
        sys.exit(2)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    n_chips = 4 if args.four_chips else 1
    device = device_info(n_chips)
    cache_dir = enable_compile_cache()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"[cache] persistent compilation cache {cache_dir}: {entries} "
        f"entries at start ({'warm' if entries else 'cold'})")
    t0 = time.perf_counter()
    if args.four_chips:
        phase_sharded_scenarios(n_chips)
        phase_sharded_fleet(n_chips)
    else:
        phase_closed_loop()
        phase_des_vs_cpu()
        phase_whatif()
        phase_service()
    log(f"[done] all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
