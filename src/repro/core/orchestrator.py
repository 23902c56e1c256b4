"""The Orchestrator (paper §2.3, component C) — now a thin imperative shell.

All per-window math lives in the pure functional core
(:mod:`repro.core.state`): a pytree :class:`~repro.core.state.TwinState`
advanced by the jitted :func:`~repro.core.state.twin_step`.  This shell owns
only what a pure function cannot: telemetry I/O (the
:class:`~repro.core.telemetry.TelemetryStore`), wall-clock pacing
(acceleration factor), run metadata (:class:`WindowRecord` — "which outputs
belong together", §2.3), float64 sustainability bookkeeping, and the
SLO-aware proposals routed through the human-in-the-loop gate.

The split is behavior-preserving: the shell reproduces the pre-redesign
per-window MAPE, parameter stream and gCO2 records bit-for-bit (pinned by
``tests/golden/orchestrator_pre_core.npz``), while the core it delegates to
additionally composes with ``vmap`` (fleets of twins,
``repro.core.twin.run_fleet``) and ``scan``.

Acceleration factor (paper §2.3): ratio between simulated and wall time.
  * factor=1   — live twinning: the loop sleeps out each window's wall time.
  * factor>1   — fixed acceleration.
  * factor=None — maximum acceleration (as fast as compute allows).
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.calibrate import CalibrationSpec
from repro.core.desim import PLACEMENT_POLICIES, Prediction, SimOutput, simulate_utilization
from repro.core.feedback import (
    HITLGate,
    Proposal,
    ProposalKind,
    propose_from_optimum,
    propose_from_scenario,
    propose_from_state,
)
from repro.core.optimize import (
    ObjectiveSpec,
    OptimizeResult,
    OptimizerConfig,
    SearchSpace,
    optimize,
)
from repro.core.power import PowerParams, mape
from repro.core.scenarios import Scenario, ScenarioSummary, evaluate_scenarios
from repro.core.state import (
    SimSlice,
    TwinConfig,
    TwinState,
    empty_telemetry,
    init_twin_state,
    load_state,
    make_telemetry,
    save_state,
    twin_step_jit,
)
from repro.traces.carbon import validate_carbon_intensity
from repro.traces.price import validate_price
from repro.traces.thermal import PUEParams, validate_ambient
from repro.core.slo import NFR1, BiasTracker, SLOMonitor
from repro.core.telemetry import (
    AMBIENT_KEY,
    CARBON_INTENSITY_KEY,
    PRICE_KEY,
    TelemetryStore,
    TelemetryWindow,
)
from repro.traces.schema import SAMPLE_SECONDS, DatacenterConfig, Workload


@dataclasses.dataclass(frozen=True)
class OrchestratorConfig:
    bins_per_window: int = 36            # 3 h windows at 5-min sampling
    calibration: CalibrationSpec = CalibrationSpec()
    calibrate: bool = True               # E2 ablation switch
    history_windows: int = 4             # telemetry history per calibration
    acceleration: float | None = None    # None = max acceleration (paper mode 3)
    power_cap_w: float | None = None
    power_model: str = "opendc"
    kernel_backend: str = "xla"          # "pallas" on TPU deployments
    #: facility PUE model: window predictions and what-if sweeps report
    #: facility power (IT x PUE(load, ambient)) instead of bare IT draw.
    #: Scenarios that set their own ``pue_base`` override this default.
    pue: PUEParams | None = None
    #: resident-DES mode (paper stage 3): the full-horizon utilization field
    #: lives *inside* ``TwinState.sim_u`` and ``twin_step`` slices its own
    #: window, so an applied topology/scheduler proposal
    #: (:meth:`Orchestrator.apply_proposal`) re-seeds the twin's own
    #: simulation instead of an external cache.  Off by default: the
    #: external-cache path stays bitwise-pinned by the goldens.
    sim_in_state: bool = False


@dataclasses.dataclass(frozen=True)
class Clock:
    """Injectable wall clock for the I/O shell (tracecheck TC007).

    ``now``/``sleep`` default to the real clock; pacing tests inject fakes
    so acceleration behavior is asserted deterministically instead of
    slept out.  The pure core never sees this object — wall time only
    touches records and pacing, never the traced math.
    """

    now: Callable[[], float] = time.time
    sleep: Callable[[float], None] = time.sleep


@dataclasses.dataclass
class WindowRecord:
    """Run metadata the orchestrator records per window (paper §2.3:
    'which outputs belong together').

    ``started_at`` and ``sim_seconds`` are on the shell's :class:`Clock`:
    ``sim_seconds`` times the fused ``twin_step`` (prediction and
    calibration are one program) up to its prediction being ready.  They
    stay for the benchmark's ``host_ms.twin``; where a window's time goes is
    read from the profiler's ``opendt.window.*`` spans instead.

    ``host_reads`` counts the device-to-host reads of a ``jax.Array`` the
    window made, and ``host_read_bytes`` their size on the device.
    """

    window: int
    started_at: float
    sim_seconds: float
    params: PowerParams
    prediction: Prediction
    mape: float | None = None        # filled when telemetry lands
    gco2: float | None = None        # window carbon (needs intensity trace)
    energy_cost: float | None = None  # window cost in $ (needs price trace)
    proposals: int = 0
    host_reads: int = 0
    host_read_bytes: int = 0


class _HostReads:
    """Counts a window's device-to-host reads of ``jax.Array`` values.

    Each call does exactly the ``np.asarray``/``float`` it stands for; a
    host value passes through uncounted.
    """

    def __init__(self):
        self.count = 0
        self.nbytes = 0

    def _seen(self, x) -> None:
        if isinstance(x, jax.Array):
            self.count += 1
            self.nbytes += x.nbytes

    def array(self, x, dtype=None) -> np.ndarray:
        self._seen(x)
        return np.asarray(x, dtype)

    def scalar(self, x) -> float:
        self._seen(x)
        return float(x)


#: per-orchestrator id carried by its profiler spans (``twin=``)
_TRACE_IDS = itertools.count()


@dataclasses.dataclass(frozen=True)
class WhatIfResult:
    """Outcome of one batched what-if sweep.

    ``summaries[0]`` is the baseline (current topology) when the sweep was
    run with ``include_baseline=True``; with ``include_baseline=False`` the
    summaries are the user's scenarios only (the baseline is still evaluated
    internally so every candidate — including the first — is compared
    against the *current* configuration, never against another candidate).
    ``proposals`` are already submitted to the orchestrator's HITL gate.
    """

    summaries: list[ScenarioSummary]
    proposals: list[Proposal]
    sim: SimOutput              # batched, leaves [S, ...]
    prediction: Prediction      # batched, leaves [S, ...]


@dataclasses.dataclass(frozen=True)
class OptimizeWhatIfResult:
    """Outcome of one searched what-if: the optimum plus its HITL routing.

    ``result`` is the raw :class:`~repro.core.optimize.OptimizeResult`
    (incumbent, baseline, full evaluation history, convergence trace);
    ``proposals`` are already submitted to the orchestrator's HITL gate and
    carry the searched optimum's objective breakdown vs the baseline in
    their ``impact``.
    """

    result: OptimizeResult
    proposals: list[Proposal]


class Orchestrator:
    """Drives the closed loop over a trace-driven physical twin.

    The physical twin is abstracted as the TelemetryStore producer —
    experiments push synthesized ground truth; a live deployment pushes
    its measured telemetry the same way.

    The windowed math is one ``twin_step`` per window on ``self.state``;
    this object is the I/O shell around it.
    """

    def __init__(
        self,
        workload: Workload,
        dc: DatacenterConfig,
        t_bins: int,
        cfg: OrchestratorConfig = OrchestratorConfig(),
        base_params: PowerParams = PowerParams(),
        gate: HITLGate | None = None,
        carbon_intensity: "np.ndarray | None" = None,
        ambient_c: "np.ndarray | None" = None,
        price: "np.ndarray | None" = None,
        clock: Clock | None = None,
    ):
        self.workload = workload
        self.dc = dc
        self.t_bins = int(t_bins)
        self.cfg = cfg
        self.base_params = base_params
        # full-horizon forecasts ([t_bins] each): grid carbon intensity
        # (gCO2/kWh), outside-air temperature (deg C, feeds the dynamic-PUE
        # model) and electricity spot price ($/kWh).  Window predictions
        # gain gCO2 / facility PUE / energy cost and what-if sweeps become
        # carbon-, cooling- and cost-aware.  Per-window *measured* values in
        # telemetry extras (telemetry.CARBON_INTENSITY_KEY / AMBIENT_KEY /
        # PRICE_KEY) override these forecasts when scoring a window.
        if carbon_intensity is not None:
            carbon_intensity = validate_carbon_intensity(
                np.asarray(carbon_intensity), self.t_bins)
        self.carbon_intensity = carbon_intensity
        if ambient_c is not None:
            ambient_c = validate_ambient(np.asarray(ambient_c), self.t_bins)
        self.ambient_c = ambient_c
        if price is not None:
            price = validate_price(np.asarray(price), self.t_bins)
        self.price = price
        if (cfg.pue is not None and cfg.pue.amb_coeff > 0.0
                and ambient_c is None):
            raise ValueError(
                "OrchestratorConfig.pue has amb_coeff > 0 but no ambient_c "
                "trace was supplied — pass ambient_c=[t_bins] deg C or use "
                "a load-only PUE model (amb_coeff=0)")
        self.clock = clock or Clock()
        self.trace_id = next(_TRACE_IDS)
        self.store = TelemetryStore(cfg.bins_per_window)
        self.gate = gate or HITLGate()
        self.records: list[WindowRecord] = []
        # scheduler knobs the resident DES runs under; structural proposals
        # (apply_proposal) are the only writers after construction.
        self.policy: str | None = None
        self.backfill_depth: int = 0
        self._sim: SimOutput | None = None
        self.twin_cfg = TwinConfig(
            bins_per_window=cfg.bins_per_window,
            dc=dc,
            calibration=cfg.calibration,
            calibrate=cfg.calibrate,
            history_windows=cfg.history_windows,
            power_model=cfg.power_model,
            kernel_backend=cfg.kernel_backend,
            slos=(NFR1,),
            pue=cfg.pue,
            sim_bins=self.t_bins if cfg.sim_in_state else 0,
        )
        sim_u = self._ensure_sim().u_th if cfg.sim_in_state else None
        self.state: TwinState = init_twin_state(self.twin_cfg, base_params,
                                                sim_u=sim_u)

    # -- pure-core views ------------------------------------------------------
    @property
    def monitor(self) -> SLOMonitor:
        """SLO compliance view, hydrated from the core's accumulators."""
        return SLOMonitor.from_counts(
            self.twin_cfg.slos, self.state.slo_samples,
            self.state.slo_compliant)

    @property
    def bias(self) -> BiasTracker:
        """Fig.-6 bias split, hydrated from the core's accumulators."""
        return BiasTracker(under=int(self.state.bias_under),
                           over=int(self.state.bias_over),
                           ties=int(self.state.bias_ties))

    def save_state(self, path: str) -> None:
        """Checkpoint the twin core (see :func:`repro.core.state.save_state`)."""
        save_state(self.state, path)

    def restore_state(self, path: str) -> None:
        """Resume from a checkpoint; the config must match this orchestrator."""
        state = load_state(path)
        if state.cfg != self.twin_cfg:
            raise ValueError(
                "checkpointed TwinConfig differs from this orchestrator's "
                f"configuration:\n  saved: {state.cfg}\n  here:  {self.twin_cfg}")
        self.state = state

    # -- simulation engine (component H) ------------------------------------
    def _ensure_sim(self) -> SimOutput:
        """Trace-driven utilization simulation for the full horizon.

        Deterministic and power-parameter independent, so it is computed once
        and windows read slices — the DES itself re-runs only when the
        workload or topology changes (what-if analysis does exactly that).
        """
        if self._sim is None:
            with TraceAnnotation("opendt.des", twin=self.trace_id):
                self._sim = simulate_utilization(
                    self.workload,
                    num_hosts=self.dc.num_hosts,
                    cores_per_host=self.dc.cores_per_host,
                    t_bins=self.t_bins,
                    policy=self.policy,
                    backfill_depth=self.backfill_depth,
                )
        return self._sim

    def invalidate(self) -> None:
        """Drop the cached DES state (topology/workload changed)."""
        self._sim = None

    @property
    def num_windows(self) -> int:
        return self.t_bins // self.cfg.bins_per_window

    def window_slice(self, window: int) -> slice:
        w = self.cfg.bins_per_window
        return slice(window * w, (window + 1) * w)

    # -- one window of operation --------------------------------------------
    def run_window(self, window: int) -> WindowRecord:
        """Execute one window: gather its inputs, advance the pure core one
        ``twin_step`` (predict S_k with params from C_{k-1}; score + calibrate
        C_k when telemetry has landed), then do the shell work — records,
        float64 carbon bookkeeping, proposals, pacing.

        Profiler spans (``window=``, ``twin=`` as metadata): ``opendt.window``
        around the whole window, and inside it ``opendt.window.inputs``,
        ``.step`` (the call of the jitted step), ``.wait`` (until its
        prediction is ready) and ``.readback``, which cover its body."""
        span = dict(window=window, twin=self.trace_id)
        with TraceAnnotation("opendt.window", **span):
            return self._run_window(window, span)

    def _run_window(self, window: int, span: dict) -> WindowRecord:
        reads = _HostReads()
        t_start = self.clock.now()
        with TraceAnnotation("opendt.window.inputs", **span):
            sim = self._ensure_sim()
            sl = self.window_slice(window)

            # Telemetry for this window (produced asynchronously by the
            # physical twin; in-loop experiments ingest it before calling
            # run_window).
            tw = self.store.get(window)
            # Telemetry measured on a *different* topology (ingested before
            # an apply_proposal resize) cannot score this twin — same
            # not-landed treatment as missing telemetry, never a shape error
            # inside jit.
            if (tw is not None
                    and reads.array(tw.u_th).shape[1] != self.dc.num_hosts):
                tw = None
            # window carbon: prefer *measured* intensity from telemetry
            # extras over the configured forecast (same precedence as power
            # itself).
            ci_meas = (tw.extras.get(CARBON_INTENSITY_KEY)
                       if tw is not None else None)
            if (ci_meas is not None
                    and reads.array(ci_meas).shape[0] != (sl.stop - sl.start)):
                ci_meas = None  # partially-clipped extras: use the forecast
            if ci_meas is not None:
                # same boundary rule as the forecast: a NaN/negative measured
                # intensity (sensor glitch) must fail loudly, not flip the
                # sign of the sustainability record.
                ci_meas = validate_carbon_intensity(reads.array(ci_meas))

            # measured spot price / ambient from telemetry extras, same
            # shape-check fallback and loud validation as carbon above.
            w_bins = sl.stop - sl.start
            pr_meas = tw.extras.get(PRICE_KEY) if tw is not None else None
            if (pr_meas is not None
                    and reads.array(pr_meas).shape[0] != w_bins):
                pr_meas = None
            if pr_meas is not None:
                pr_meas = validate_price(reads.array(pr_meas))
            amb_meas = (tw.extras.get(AMBIENT_KEY) if tw is not None
                        else None)
            if (amb_meas is not None
                    and reads.array(amb_meas).shape[0] != w_bins):
                amb_meas = None
            if amb_meas is not None:
                amb_meas = validate_ambient(reads.array(amb_meas))

            ci_w = (jnp.asarray(self.carbon_intensity[sl], jnp.float32)
                    if self.carbon_intensity is not None else None)
            # ambient feeds the *prediction* itself (PUE multiplies power),
            # so a measured trace replaces the forecast slice before the
            # step runs — a value-level swap, same shapes, no retrace.
            amb_host = (amb_meas if amb_meas is not None
                        else (self.ambient_c[sl]
                              if self.ambient_c is not None else None))
            amb_w = (jnp.asarray(amb_host, jnp.float32)
                     if amb_host is not None else None)
            pr_w = (jnp.asarray(self.price[sl], jnp.float32)
                    if self.price is not None else None)
            telem = (make_telemetry(tw.u_th, tw.power_w) if tw is not None
                     else empty_telemetry(self.cfg.bins_per_window,
                                          self.dc.num_hosts))
            # In resident-DES mode the step slices its own window from
            # ``state.sim_u`` (u_th=None), so what it predicts from is
            # whatever apply_proposal last seeded — not this shell's cache.
            sim_slice = SimSlice(u_th=(None if self.cfg.sim_in_state
                                       else sim.u_th[sl]),
                                 carbon_intensity=ci_w, ambient_c=amb_w,
                                 price=pr_w)

        # All the math: one pure, jitted step on the twin core.
        t0 = self.clock.now()
        with TraceAnnotation("opendt.window.step", **span):
            self.state, out = twin_step_jit(self.state, telem, sim_slice)
        pred = out.prediction
        with TraceAnnotation("opendt.window.wait", **span):
            pred.power_w.block_until_ready()
        sim_seconds = self.clock.now() - t0

        with TraceAnnotation("opendt.window.readback", **span):
            rec = WindowRecord(
                window=window, started_at=t_start, sim_seconds=sim_seconds,
                params=out.params_used, prediction=pred,
            )

            # float64 sustainability record (host-side reporting precision).
            if ci_meas is not None:
                rec.gco2 = float(np.sum(
                    reads.array(pred.energy_kwh, np.float64)
                    * reads.array(ci_meas, np.float64)))
            elif pred.gco2 is not None:
                rec.gco2 = float(np.sum(reads.array(pred.gco2, np.float64)))

            # float64 energy-cost record: measured spot price wins over the
            # forecast the traced lane priced with.
            if pr_meas is not None:
                rec.energy_cost = float(np.sum(
                    reads.array(pred.energy_kwh, np.float64)
                    * reads.array(pr_meas, np.float64)))
            elif pred.energy_cost is not None:
                rec.energy_cost = float(np.sum(
                    reads.array(pred.energy_cost, np.float64)))

            if tw is not None:
                rec.mape = reads.scalar(out.mape)

                # SLO-aware proposals through the HITL gate.
                props = propose_from_state(
                    window,
                    mape=rec.mape,
                    mean_util=reads.scalar(np.mean(tw.u_th)),
                    queue_len=float(np.mean(reads.array(sim.queue_len[sl]))),
                    power_w=float(np.mean(reads.array(pred.power_w))),
                    power_cap_w=self.cfg.power_cap_w,
                )
                for p_ in props:
                    self.gate.submit(p_)
                rec.proposals = len(props)

            rec.host_reads, rec.host_read_bytes = reads.count, reads.nbytes
            self.records.append(rec)

            # acceleration factor: live mode sleeps out the window's wall
            # time.
            if self.cfg.acceleration:
                wall = (self.cfg.bins_per_window * SAMPLE_SECONDS
                        / self.cfg.acceleration)
                spent = self.clock.now() - t_start
                if wall > spent:
                    # capped for tests
                    self.clock.sleep(min(wall - spent, 1.0))
        return rec

    # -- batched what-if analysis (paper Fig. 1, operator loop) --------------
    def evaluate_whatif(
        self,
        scenarios: "list[Scenario] | tuple[Scenario, ...]",
        *,
        include_baseline: bool = True,
        max_hosts: int | None = None,
    ) -> "WhatIfResult":
        """Evaluate S candidate configurations in one jitted program.

        Uses the *calibrated* power parameters (the twin's current best model
        of reality) so what-if outcomes reflect the live datacenter, not the
        spec sheet.  A baseline scenario (the current topology and scheduler
        — worst-fit FCFS, no backfill) is always evaluated alongside the
        candidates and **every** user scenario is compared against it; each
        candidate that improves a sustainability metric without breaking
        SLOs, cuts queue wait via a cheaper *scheduler* (placement policy /
        backfill depth, a software-only change), or violates its power cap
        becomes a proposal routed through the HITL gate.

        ``include_baseline`` only controls whether the baseline appears in
        the returned ``summaries``/``sim``/``prediction`` (as entry 0) — it
        never changes which scenarios generate proposals.  (Before this fix,
        ``include_baseline=False`` silently treated the *first user scenario*
        as the baseline and excluded it from proposal generation.)  Because
        the baseline always rides along, an explicit ``max_hosts`` is raised
        to at least the current topology's host count (the padded host axis
        must fit the baseline; per-lane outputs are unaffected).
        """
        params = self.state.params
        scs = [self._with_pue(s)
               for s in [Scenario(name="baseline")] + list(scenarios)]
        if max_hosts is not None:
            max_hosts = max(int(max_hosts), self.dc.num_hosts)
        _, sim, pred, summaries = evaluate_scenarios(
            self.workload, self.dc, scs,
            t_bins=self.t_bins, base_params=params, max_hosts=max_hosts,
            model=self.cfg.power_model,
            carbon_intensity=self.carbon_intensity,
            ambient_c=self.ambient_c,
            price=self.price,
        )
        window = len(self.records)
        baseline = summaries[0]
        proposals: list[Proposal] = []
        for s in summaries[1:]:
            for p in propose_from_scenario(window, s, baseline):
                proposals.append(self.gate.submit(p))
        if not include_baseline:
            sim = jax.tree.map(lambda x: x[1:], sim)
            pred = jax.tree.map(lambda x: x[1:], pred)
            summaries = summaries[1:]
        return WhatIfResult(summaries=summaries, proposals=proposals,
                            sim=sim, prediction=pred)

    # -- applying approved proposals (paper stage 3, closing the loop) -------
    def apply_proposal(self, p: Proposal) -> None:
        """Apply an approved structural proposal to this twin.

        Closes the paper's operator loop: a what-if/optimize sweep produced
        the proposal, the HITL gate approved it, and this call makes the
        twin *be* the proposed datacenter.  ``SCHEDULER_CHANGE`` swaps the
        DES scheduler (placement policy + backfill depth, a software-only
        change); ``SCALE_UP`` / ``SCALE_DOWN_IDLE`` resize the topology.
        The full-horizon DES then re-runs under the new configuration and
        the twin core is rebuilt around it (:meth:`_rebuild_state`) — in
        resident-DES mode (``cfg.sim_in_state``) that re-seeds the state's
        own ``sim_u``, so the very next ``twin_step`` predicts the new
        datacenter without this shell re-slicing anything.

        Raises for unapproved proposals (route them through the gate first)
        and for kinds with no structural interpretation here (power caps and
        time-shifting live on the scenario axis, not the twin's topology).
        """
        if p.approved is not True:
            raise ValueError(
                f"proposal {p.kind.value}@w{p.window} is not approved — "
                "route it through the HITL gate before applying")
        if p.kind is ProposalKind.SCHEDULER_CHANGE:
            self.policy = p.impact.get("policy", self.policy)
            self.backfill_depth = int(
                p.impact.get("backfill_depth", self.backfill_depth))
        elif p.kind in (ProposalKind.SCALE_UP, ProposalKind.SCALE_DOWN_IDLE):
            if "num_hosts" not in p.impact:
                raise ValueError(
                    f"{p.kind.value} proposal carries no num_hosts impact")
            n = int(p.impact["num_hosts"])
            if n <= 0:
                raise ValueError(f"proposed num_hosts must be >= 1; got {n}")
            self.dc = dataclasses.replace(self.dc, num_hosts=n)
        else:
            raise ValueError(
                f"{p.kind.value} is not a structural proposal this twin can "
                "apply (power caps / load shifting are scenario axes; "
                "recalibration is automatic)")
        p.applied = True
        self.invalidate()
        self._rebuild_state()

    def _rebuild_state(self) -> None:
        """Rebuild the twin core around the current ``self.dc`` / scheduler.

        Run accumulators (window counter, SLO counts, bias split) always
        migrate — they describe the run, not the topology.  Calibrated
        parameters migrate too and become the new base (per-host rows keep
        their first ``min(old, new)`` hosts and mean-pad growth, the same
        convention as the what-if path).  Calibration history migrates only
        while the host axis is unchanged: telemetry measured on a different
        topology would mis-calibrate the new one, so a resize starts the
        history fresh.  In resident-DES mode the rebuilt state is seeded
        with the re-run DES horizon.
        """
        old = self.state
        old_h = old.cfg.dc.num_hosts
        h = self.dc.num_hosts
        self.twin_cfg = dataclasses.replace(self.twin_cfg, dc=self.dc)
        sim_u = self._ensure_sim().u_th if self.cfg.sim_in_state else None

        def row(x):
            v = np.asarray(x, np.float32)
            if v.ndim == 0:
                return v
            out = np.full((h,), float(v.mean()), np.float32)
            out[:min(v.size, h)] = v[:h]
            return out

        params = PowerParams(p_idle=row(old.params.p_idle),
                             p_max=row(old.params.p_max),
                             r=row(old.params.r))
        state = init_twin_state(self.twin_cfg, params, sim_u=sim_u)
        keep = dict(window=old.window,
                    slo_samples=old.slo_samples,
                    slo_compliant=old.slo_compliant,
                    bias_under=old.bias_under,
                    bias_over=old.bias_over,
                    bias_ties=old.bias_ties)
        if h == old_h:
            keep.update(hist_u=old.hist_u, hist_p=old.hist_p,
                        hist_n=old.hist_n)
        self.state = dataclasses.replace(state, **keep)

    def _with_pue(self, s: Scenario) -> Scenario:
        """Apply the orchestrator's facility PUE model to a scenario.

        Scenarios that set their own ``pue_base`` keep it; with
        ``cfg.pue=None`` this is the identity.  Applying the default to
        *every* lane (baseline included) keeps what-if comparisons
        facility-vs-facility, never facility-vs-bare-IT.
        """
        p = self.cfg.pue
        if p is None or s.pue_base is not None:
            return s
        return dataclasses.replace(
            s, pue_base=p.base, pue_amb_coeff=p.amb_coeff,
            pue_amb_ref=p.amb_ref, pue_load_coeff=p.load_coeff)

    # -- searched what-if: optimize over the scenario space ------------------
    def default_search_space(self) -> SearchSpace:
        """A conservative software-only search space for the current twin.

        Structures: the current topology under every placement policy (the
        non-default policies get a backfill window — a pure scheduler
        change); continuous axes: deferrable-job time-shifting up to 3 hours.
        Cap axes stay off by default — capping trades performance for watts
        and deserves an explicitly chosen range (pass a custom
        :class:`~repro.core.optimize.SearchSpace` to search them).
        """
        structures = tuple(
            Scenario(name=p, policy=p,
                     backfill_depth=0 if p == "worst_fit" else 4)
            for p in sorted(PLACEMENT_POLICIES))
        return SearchSpace(structures=structures, shift_bins=(0, 36))

    def optimize_whatif(
        self,
        space: SearchSpace | None = None,
        objective: ObjectiveSpec | None = None,
        *,
        key: "int | jax.Array" = 0,
        config: OptimizerConfig = OptimizerConfig(),
        shard: bool = False,
        mesh=None,
    ) -> OptimizeWhatIfResult:
        """Search the scenario space and route the optimum through the gate.

        Where :meth:`evaluate_whatif` scores a hand-written candidate list,
        this *finds* the operating point: the search space defaults to
        :meth:`default_search_space` and is evaluated against the twin's
        **current calibrated** power parameters (``self.state.params``) and
        carbon forecast, so the optimum reflects the live datacenter, not
        the spec sheet.  The winner is compared against the always-evaluated
        baseline and submitted to the HITL gate via
        :func:`repro.core.feedback.propose_from_optimum` — proposals carry
        the searched optimum plus its objective breakdown vs baseline.
        Deterministic given ``key``; ``shard=True`` spans the device mesh.
        """
        if space is None:
            space = self.default_search_space()
        if self.cfg.pue is not None:
            space = dataclasses.replace(
                space,
                structures=tuple(self._with_pue(s) for s in space.structures))
        if objective is None:
            # no carbon forecast -> optimize energy instead of gCO2 (the
            # gCO2 weight would otherwise demand a trace we don't have)
            objective = (ObjectiveSpec() if self.carbon_intensity is not None
                         else ObjectiveSpec(w_gco2_kg=0.0, w_energy_kwh=1.0))
        res = optimize(
            self.workload, self.dc, space, objective,
            t_bins=self.t_bins, base_params=self.state.params,
            carbon_intensity=self.carbon_intensity,
            ambient_c=self.ambient_c, price=self.price,
            key=key, config=config,
            model=self.cfg.power_model, shard=shard, mesh=mesh,
        )
        window = len(self.records)
        proposals = [
            self.gate.submit(p) for p in propose_from_optimum(
                window, res.best_summary, res.baseline_summary,
                objective=res.best.objective,
                baseline_objective=res.baseline.objective,
                breakdown=res.best.breakdown,
                baseline_breakdown=res.baseline.breakdown,
            )]
        return OptimizeWhatIfResult(result=res, proposals=proposals)

    def run(self, num_windows: int | None = None) -> list[WindowRecord]:
        n = num_windows if num_windows is not None else self.num_windows
        for w in range(n):
            self.run_window(w)
        return self.records

    # -- results -------------------------------------------------------------
    def overall_mape(self) -> float:
        """MAPE over all scored bins (concatenated windows)."""
        real, simp = [], []
        for rec in self.records:
            tw = self.store.get(rec.window)
            if tw is None:
                continue
            real.append(tw.power_w)
            simp.append(np.asarray(rec.prediction.power_w, np.float64))
        if not real:
            return float("nan")
        return float(mape(jnp.asarray(np.concatenate(real)),
                          jnp.asarray(np.concatenate(simp))))

    def per_window_mape(self) -> np.ndarray:
        return np.array([r.mape if r.mape is not None else np.nan
                         for r in self.records])
