"""Batched what-if scenario engine (paper Fig. 1, operator loop).

What-if analysis re-simulates the same trace against S candidate
configurations — topologies (host count, cores per host), **placement
policies** (first/best/worst/random-fit, backfill depth), power-model
parameters, **enforced power caps** (static and carbon-aware,
``cap_t = base + slope * intensity_t``), workload perturbations including
**deferrable-job time-shifting** — and compares SLO and sustainability
outcomes (energy, power, **gCO2** against a grid carbon-intensity trace)
before any hardware moves.  The naive loop pays S
trace + compile + run cycles; since the masked DES core
(:func:`repro.core.desim.simulate_utilization_masked`) is shape-identical
across candidates once the host axis is padded to a static ``max_hosts``,
and the scheduler is a *traced* ``policy_id``/``backfill_depth`` pair, the
whole sweep is **one jitted program**: ``jax.vmap`` over a stacked scenario
pytree, one compilation for any S — including (policies x topologies) grids.

Pipeline::

    [Scenario, ...]  --build_scenario_set-->  ScenarioSet (leaves [S, ...])
    ScenarioSet      --run_scenarios------->  SimOutput + Prediction ([S, ...])
    ScenarioSet      --evaluate_scenarios-->  [ScenarioSummary] (host-side)

``Orchestrator.evaluate_whatif`` wires the summaries into SLO-aware
proposals through the HITL gate (``feedback.propose_from_scenario``),
including scheduler-change recommendations.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.desim import (
    POLICY_NAMES,
    Prediction,
    SimOutput,
    resolve_policy,
    simulate_utilization_masked,
)
from repro.core.power import (
    PowerParams,
    carbon_gco2,
    datacenter_power,
    energy_kwh,
)
from repro.traces.carbon import validate_carbon_intensity
from repro.traces.schema import (
    SAMPLE_SECONDS,
    DatacenterConfig,
    Workload,
    host_mask,
)

Array = jax.Array

#: above this many total [S, jobs, bins] elements the batched read-out is
#: chunked over time (see desim._READOUT_BLOCK) — ~128 MB per dense float32
#: intermediate at the threshold, a few of which are live simultaneously.
_BATCH_READOUT_THRESHOLD = 32_000_000


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One what-if candidate.  ``None`` fields inherit the base config.

    Axes:
      * **Topology** — ``num_hosts`` / ``cores_per_host`` (defaults: the base
        :class:`~repro.traces.schema.DatacenterConfig`).
      * **Scheduler** — ``policy`` is a placement-policy name from
        :data:`repro.core.desim.PLACEMENT_POLICIES` (``"first_fit"``,
        ``"best_fit"``, ``"worst_fit"``, ``"random_fit"``; ``None`` means
        worst-fit, the seed scheduler) and ``backfill_depth`` lets up to that
        many queued successors start ahead of a capacity-blocked FCFS head
        (0 = strict head-of-line blocking).  Both become *traced* scalars,
        so a scheduler sweep shares one compilation with a topology sweep.
      * **Power model** — ``p_idle`` / ``p_max`` / ``r`` override the
        calibrated parameters.  Invalid overrides (``r <= 0``,
        ``p_max < p_idle``) raise at construction — they would otherwise
        produce negative watts (see ``power.validate_power_params``).
      * **Power cap** — ``power_cap_w`` is a static facility cap, now
        *enforced* in the read-out (delivered power is clipped to the cap
        and performance metrics are throttled accordingly, not merely
        flagged); ``carbon_cap_base_w``/``carbon_cap_slope`` add a
        carbon-aware cap ``base + slope * intensity_t`` (slope in W per
        gCO2/kWh, usually negative: dirtier grid -> tighter cap).  The
        effective per-bin cap is the minimum of the two.  Carbon-aware caps
        require a ``carbon_intensity`` trace at run time.
      * **Workload** — multiplicative knobs on the shared base trace:
        ``arrival_scale`` compresses submission times (×k arrival rate),
        ``duration_scale`` stretches runtimes, ``util_scale`` scales the
        per-phase utilization profiles (clipped to [0, 1]), and
        ``shift_bins`` time-shifts *deferrable* jobs (see
        ``Workload.deferrable``; default: all jobs) by that many 5-minute
        bins — positive delays work into later (e.g. cleaner-grid) bins.
      * **Failures** — ``failures`` is a tuple of
        :class:`repro.runtime.fault.HostFailure` windows: during
        ``[start_bin, end_bin)`` the host accepts no placements; an
        ``"outage"`` additionally kills its running jobs (cores return at
        ``end_bin``) and draws no power, a ``"degraded"`` host drains.
        One window per host; windows must start inside the horizon
        (checked at :func:`run_scenarios`, where ``t_bins`` is known).
      * **Dynamic PUE** — ``pue_base`` (>= 1) switches the cooling model
        on: facility power becomes IT power times
        ``pue_base + pue_amb_coeff * max(ambient_t - pue_amb_ref, 0)
        + pue_load_coeff * (1 - util_t)`` (see
        :func:`repro.traces.thermal.dynamic_pue`).  Caps, energy, gCO2
        and cost then price the cooling overhead.  Coefficients without
        ``pue_base`` are rejected — a silent half-enabled axis.

    All knobs stack into ``[S]`` (or ``[S, H]``) tensors or per-scenario
    workload copies of identical shape, so a (failures × PUE × caps ×
    shifts × topologies) grid still compiles **once** (see
    :func:`run_scenarios`).

    >>> Scenario(name="bf", policy="best_fit", backfill_depth=4).policy
    'best_fit'
    >>> Scenario().backfill_depth        # default: strict FCFS worst-fit
    0
    >>> Scenario(r=0.0)
    Traceback (most recent call last):
        ...
    ValueError: scenario '': power-model exponent r must be > 0, got 0.0
    >>> Scenario(backfill_depth=40)
    Traceback (most recent call last):
        ...
    ValueError: scenario '': backfill_depth must be in [0, 31] (uint32 skip-mask width), got 40
    >>> Scenario(pue_base=0.9)
    Traceback (most recent call last):
        ...
    ValueError: scenario '': pue_base must be finite and >= 1 (facility/IT power ratio), got 0.9
    >>> Scenario(pue_load_coeff=0.2)
    Traceback (most recent call last):
        ...
    ValueError: scenario '': PUE coefficients set without pue_base — set pue_base (>= 1) to enable the dynamic-PUE axis
    """

    name: str = ""
    num_hosts: int | None = None
    cores_per_host: int | None = None
    policy: str | int | None = None
    backfill_depth: int = 0
    p_idle: float | None = None
    p_max: float | None = None
    r: float | None = None
    power_cap_w: float | None = None
    carbon_cap_base_w: float | None = None
    carbon_cap_slope: float = 0.0
    arrival_scale: float = 1.0
    duration_scale: float = 1.0
    util_scale: float = 1.0
    shift_bins: int = 0
    failures: tuple = ()
    pue_base: float | None = None
    pue_amb_coeff: float = 0.0
    pue_amb_ref: float = 18.0
    pue_load_coeff: float = 0.0

    def __post_init__(self):
        # the Scenario boundary is host-side and concrete: bad power-model
        # parameters must never survive long enough to emit negative watts.
        if self.r is not None and not (math.isfinite(self.r) and self.r > 0):
            raise ValueError(
                f"scenario {self.name!r}: power-model exponent r must be "
                f"> 0, got {self.r}")
        if self.p_idle is not None and not (math.isfinite(self.p_idle)
                                            and self.p_idle >= 0):
            raise ValueError(
                f"scenario {self.name!r}: p_idle must be finite and >= 0 W, "
                f"got {self.p_idle}")
        if self.p_max is not None and not math.isfinite(self.p_max):
            raise ValueError(
                f"scenario {self.name!r}: p_max must be finite W, "
                f"got {self.p_max}")
        if (self.p_idle is not None and self.p_max is not None
                and self.p_max < self.p_idle):
            raise ValueError(
                f"scenario {self.name!r}: p_max ({self.p_max}) < p_idle "
                f"({self.p_idle}) inverts the power curve")
        if self.power_cap_w is not None and not self.power_cap_w > 0:
            raise ValueError(
                f"scenario {self.name!r}: power_cap_w must be > 0 W, "
                f"got {self.power_cap_w}")
        if self.carbon_cap_base_w is not None and not self.carbon_cap_base_w > 0:
            raise ValueError(
                f"scenario {self.name!r}: carbon_cap_base_w must be > 0 W, "
                f"got {self.carbon_cap_base_w}")
        if not math.isfinite(self.carbon_cap_slope):
            # a NaN/inf slope silently poisons the per-bin effective cap
            # (min with NaN is NaN in numpy, propagates to every readout)
            raise ValueError(
                f"scenario {self.name!r}: carbon_cap_slope must be finite "
                f"W per gCO2/kWh, got {self.carbon_cap_slope}")
        if not 0 <= int(self.backfill_depth) <= 31:
            # the DES skip bitmask is uint32; checked here at the concrete
            # Scenario boundary, not only in build_scenario_set, so a bad
            # depth can never reach a traced program (and a negative depth
            # is rejected instead of being silently clamped to 0)
            raise ValueError(
                f"scenario {self.name!r}: backfill_depth must be in [0, 31] "
                f"(uint32 skip-mask width), got {self.backfill_depth}")
        for knob in ("arrival_scale", "duration_scale"):
            if not getattr(self, knob) > 0:
                raise ValueError(
                    f"scenario {self.name!r}: {knob} must be > 0, "
                    f"got {getattr(self, knob)}")
        if not self.util_scale >= 0:
            raise ValueError(
                f"scenario {self.name!r}: util_scale must be >= 0, "
                f"got {self.util_scale}")
        if not isinstance(self.failures, tuple):
            object.__setattr__(self, "failures", tuple(self.failures))
        for f in self.failures:
            # duck-typed so constructing a Scenario never has to import the
            # runtime layer; HostFailure validates its own invariants
            for attr in ("host", "start_bin", "end_bin", "kind"):
                if not hasattr(f, attr):
                    raise ValueError(
                        f"scenario {self.name!r}: failures must be "
                        f"HostFailure windows, got {f!r}")
        if self.pue_base is not None and not (
                math.isfinite(self.pue_base) and self.pue_base >= 1.0):
            raise ValueError(
                f"scenario {self.name!r}: pue_base must be finite and >= 1 "
                f"(facility/IT power ratio), got {self.pue_base}")
        for knob in ("pue_amb_coeff", "pue_load_coeff"):
            v = getattr(self, knob)
            if not (math.isfinite(v) and v >= 0):
                raise ValueError(
                    f"scenario {self.name!r}: {knob} must be finite and "
                    f">= 0, got {v}")
        if not math.isfinite(self.pue_amb_ref):
            raise ValueError(
                f"scenario {self.name!r}: pue_amb_ref must be finite °C, "
                f"got {self.pue_amb_ref}")
        if self.pue_base is None and (self.pue_amb_coeff != 0.0
                                      or self.pue_load_coeff != 0.0):
            raise ValueError(
                f"scenario {self.name!r}: PUE coefficients set without "
                "pue_base — set pue_base (>= 1) to enable the dynamic-PUE "
                "axis")


@dataclasses.dataclass(frozen=True)
class ScenarioSet:
    """Device-ready stacked scenario batch (every array leaf leads with S).

    Built by :func:`build_scenario_set`; consumed by :func:`run_scenarios`.
    Shapes (``S`` scenarios, ``J`` padded jobs, ``H = max_hosts`` padded
    hosts):

    ======================  ==========================  =====================
    field                   shape / dtype               meaning
    ======================  ==========================  =====================
    ``workload``            leaves ``[S, J, ...]``      per-scenario perturbed
                                                        copies of one base
                                                        trace (padding jobs
                                                        have ``valid=False``)
    ``host_mask_s``         ``[S, H]`` bool             active-host mask;
                                                        padded hosts never run
                                                        jobs or draw power
    ``num_hosts``           ``[S]`` int32               active host count
    ``cores_per_host``      ``[S]`` int32               cores per active host
    ``policy_id``           ``[S]`` int32               placement policy (see
                                                        ``PLACEMENT_POLICIES``)
    ``backfill_depth``      ``[S]`` int32               successors that may
                                                        jump a blocked head
    ``params``              leaves ``[S, H]`` float32   per-host power-model
                                                        params (rows constant
                                                        for scalar bases)
    ``power_cap_w``         ``[S]`` float32             static cap, enforced
                                                        (+inf = uncapped)
    ``carbon_cap_base_w``   ``[S]`` float32             carbon-aware cap base
                                                        (+inf = no carbon cap)
    ``carbon_cap_slope``    ``[S]`` float32             W per gCO2/kWh; the
                                                        per-bin cap is
                                                        ``base + slope * I_t``
    ``shift_bins``          ``[S]`` int32               applied time shift
                                                        (provenance)
    ``peak_tflops``         ``[S]`` float32             topology peak
    ``fail_start``          ``[S, H]`` int32            failure-window start
                                                        bin (int32 max = the
                                                        host never fails)
    ``fail_end``            ``[S, H]`` int32            failure-window end bin
    ``fail_kill``           ``[S, H]`` bool             outage (kill jobs, no
                                                        power) vs drain
    ``pue_base``            ``[S]`` float32             dynamic-PUE base
                                                        (1.0 = identity)
    ``pue_amb_coeff``       ``[S]`` float32             PUE per °C above ref
    ``pue_amb_ref``         ``[S]`` float32             free-cooling ref °C
    ``pue_load_coeff``      ``[S]`` float32             partial-load penalty
    ======================  ==========================  =====================

    ``names`` (tuple of str), ``max_backfill`` (static int: the compile-
    time backfill window all traced depths are clipped to) and the axis
    flags ``has_failures`` / ``pue_on`` (static bools: whether the failure /
    dynamic-PUE machinery is compiled in at all) are pytree *aux data* —
    part of the jit cache key, not device arrays.  With a flag off the
    compiled program is *structurally* the pre-axis program; with it on,
    disabled lanes carry exact-identity sentinels (never-fail windows,
    PUE 1.0) and stay bit-for-bit equal to axis-off runs.  ``max_hosts``
    is implied by ``host_mask_s.shape[-1]``.
    """

    workload: Workload        # leaves [S, J, ...]
    host_mask_s: Array        # [S, max_hosts] bool
    num_hosts: Array          # [S] int32
    cores_per_host: Array     # [S] int32
    policy_id: Array          # [S] int32
    backfill_depth: Array     # [S] int32
    params: PowerParams       # leaves [S] float32
    power_cap_w: Array        # [S] float32 (+inf = uncapped)
    carbon_cap_base_w: Array  # [S] float32 (+inf = no carbon-aware cap)
    carbon_cap_slope: Array   # [S] float32 (W per gCO2/kWh)
    shift_bins: Array         # [S] int32 (provenance; already applied)
    peak_tflops: Array        # [S] float32
    fail_start: Array         # [S, max_hosts] int32 (int32 max = never)
    fail_end: Array           # [S, max_hosts] int32
    fail_kill: Array          # [S, max_hosts] bool
    pue_base: Array           # [S] float32 (1.0 = identity)
    pue_amb_coeff: Array      # [S] float32
    pue_amb_ref: Array        # [S] float32
    pue_load_coeff: Array     # [S] float32
    names: tuple[str, ...]
    max_backfill: int = 0
    has_failures: bool = False
    pue_on: bool = False

    @property
    def num_scenarios(self) -> int:
        return len(self.names)

    @property
    def max_hosts(self) -> int:
        return int(self.host_mask_s.shape[-1])


jax.tree_util.register_pytree_node(
    ScenarioSet,
    lambda s: ((s.workload, s.host_mask_s, s.num_hosts, s.cores_per_host,
                s.policy_id, s.backfill_depth, s.params, s.power_cap_w,
                s.carbon_cap_base_w, s.carbon_cap_slope, s.shift_bins,
                s.peak_tflops, s.fail_start, s.fail_end, s.fail_kill,
                s.pue_base, s.pue_amb_coeff, s.pue_amb_ref,
                s.pue_load_coeff),
               (s.names, s.max_backfill, s.has_failures, s.pue_on)),
    lambda aux, c: ScenarioSet(*c, names=aux[0], max_backfill=aux[1],
                               has_failures=aux[2], pue_on=aux[3]),
)


def _perturb(base: dict[str, np.ndarray | None],
             sc: Scenario) -> dict[str, np.ndarray | None]:
    """Apply a scenario's workload knobs (host-side numpy: build-time path).

    ``base`` holds the job-axis arrays (``submit``, ``dur``, ``util``,
    ``cores``, ``valid``, ``deferrable`` — the last possibly ``None``).
    Time-shifting moves deferrable valid jobs by ``sc.shift_bins`` bins
    (clipped at 0) and then re-sorts the job axis by the new submission
    times: the DES's FCFS queue order *is* the array order, so an unsorted
    axis would let late-shifted jobs head-block earlier work.  The stable
    sort keeps padding jobs (huge submit sentinel) at the tail and is the
    identity when nothing shifts.
    """
    out = dict(base)
    submit, dur, util = base["submit"], base["dur"], base["util"]
    if sc.arrival_scale != 1.0:
        # ×k arrival rate = submissions land k× denser on the bin axis
        submit = np.floor(
            submit.astype(np.float32) / sc.arrival_scale).astype(np.int32)
    if sc.duration_scale != 1.0:
        dur = np.maximum(
            np.ceil(dur.astype(np.float32) * sc.duration_scale), 1.0
        ).astype(np.int32)
    if sc.util_scale != 1.0:
        util = np.clip(util * sc.util_scale, 0.0, 1.0).astype(np.float32)
    out.update(submit=submit, dur=dur, util=util)
    if sc.shift_bins != 0:
        defer = base["deferrable"]
        movable = (base["valid"] if defer is None
                   else (defer & base["valid"]))
        submit = np.where(
            movable, np.maximum(submit + int(sc.shift_bins), 0), submit
        ).astype(np.int32)
        order = np.argsort(submit, kind="stable")
        out.update(
            submit=submit[order], dur=out["dur"][order],
            util=out["util"][order], cores=base["cores"][order],
            valid=base["valid"][order],
            deferrable=None if defer is None else defer[order],
        )
    return out


def _per_host_params(base_params: PowerParams, scenarios, hosts,
                     mh: int) -> PowerParams:
    """Stack power params as ``[S, max_hosts]`` rows (per-host aware).

    The base parameters may be scalars (one row value) or per-host vectors
    from calibration against a heterogeneous fleet; scenario overrides are
    scalars and replace the whole row.  Hosts beyond the base vector's
    length (scaled-up topologies, padding) assume fleet-average hardware —
    they are masked out of power/utilization unless the scenario activates
    them.  Pre-redesign this collapsed everything to per-scenario scalar
    means, silently flattening heterogeneous fleets on the what-if path
    (ROADMAP item).
    """
    def rows(field: str) -> Array:
        base_v = np.asarray(getattr(base_params, field),
                            np.float32).reshape(-1)
        base_row = np.full((mh,), float(base_v.mean()), np.float32)
        base_row[:min(base_v.size, mh)] = base_v[:mh]
        out = np.empty((len(scenarios), mh), np.float32)
        for i, sc in enumerate(scenarios):
            ov = getattr(sc, field)
            out[i] = base_row if ov is None else np.float32(ov)
        return jnp.asarray(out)

    # PowerParams validates the [S, H] stacks elementwise: a scenario that
    # overrides only p_max below the base p_idle (or vice versa) fails here.
    return PowerParams(p_idle=rows("p_idle"), p_max=rows("p_max"),
                       r=rows("r"))


def build_scenario_set(
    workload: Workload,
    dc: DatacenterConfig,
    scenarios: "list[Scenario] | tuple[Scenario, ...]",
    base_params: PowerParams = PowerParams(),
    max_hosts: int | None = None,
    max_backfill: int | None = None,
    has_failures: bool | None = None,
    pue_on: bool | None = None,
) -> ScenarioSet:
    """Stack S candidate configurations against one base trace/topology.

    Host-side (numpy) assembly: each :class:`Scenario`'s knobs are resolved
    against the base ``dc``/``base_params``, workload perturbations are
    applied to copies of the base trace, and everything is stacked into a
    device-ready :class:`ScenarioSet` whose array leaves lead with the
    scenario axis ``[S, ...]``.

    Padding semantics: the host axis is padded to ``max_hosts`` (default:
    the largest candidate host count — pass it explicitly to pin one
    compilation cache key across sweeps of different candidate mixes) and
    per-scenario activity is recorded in ``host_mask_s``; padded hosts never
    receive jobs, contribute no utilization and draw no power.  Power-model
    parameters are carried as ``[S, max_hosts]`` per-host rows, so
    heterogeneous fleets (per-host calibrated bases) survive the what-if
    path; scalar scenario overrides replace a whole row.
    The static backfill window ``max_backfill`` defaults to the max candidate
    depth, so depth-0 sweeps compile the backfill machinery out entirely;
    pass it explicitly (like ``max_hosts``) to pin one compilation cache key
    across batches whose depth mixes differ — the optimizer's generation
    loop (:mod:`repro.core.optimize`) relies on exactly this.

    The static axis flags ``has_failures`` / ``pue_on`` follow the same
    pinning convention: they default to "derived from this batch" (any
    scenario with failure windows / a ``pue_base``), and like
    ``max_hosts``/``max_backfill`` they are jit cache-key aux — pass them
    explicitly when successive batches may mix axis presence (again, the
    optimizer's generation loop).  Forcing a flag on for an axis no
    scenario uses is sound (sentinel lanes compute identical results);
    forcing one *off* while a scenario uses the axis is rejected.

    Raises ``ValueError`` on an empty scenario list, a candidate wanting
    more hosts than ``max_hosts``, a depth beyond ``max_backfill``, or a
    failure window on a host the scenario's topology does not have.
    """
    if not scenarios:
        raise ValueError("need at least one scenario")
    hosts = [sc.num_hosts if sc.num_hosts is not None else dc.num_hosts
             for sc in scenarios]
    mh = max(hosts) if max_hosts is None else int(max_hosts)
    if max(hosts) > mh:
        raise ValueError(f"scenario wants {max(hosts)} hosts > max_hosts={mh}")

    cores = [sc.cores_per_host if sc.cores_per_host is not None
             else dc.cores_per_host for sc in scenarios]
    names = tuple(sc.name or f"s{i}" for i, sc in enumerate(scenarios))

    # Every scenario perturbs the same base trace, so the stacked workload is
    # assembled host-side in numpy (one device transfer per field) — this
    # runs on every sweep and must not cost a per-scenario dispatch cascade.
    base = dict(
        submit=np.asarray(workload.submit_bin),
        dur=np.asarray(workload.duration_bins),
        util=np.asarray(workload.util_levels),
        cores=np.asarray(workload.cores),
        valid=np.asarray(workload.valid),
        deferrable=(None if workload.deferrable is None
                    else np.asarray(workload.deferrable)),
    )
    perturbed = [_perturb(base, sc) for sc in scenarios]
    wl = Workload(
        submit_bin=jnp.asarray(np.stack([p["submit"] for p in perturbed])),
        duration_bins=jnp.asarray(np.stack([p["dur"] for p in perturbed])),
        cores=jnp.asarray(np.stack([p["cores"] for p in perturbed])),
        util_levels=jnp.asarray(np.stack([p["util"] for p in perturbed])),
        valid=jnp.asarray(np.stack([p["valid"] for p in perturbed])),
        deferrable=(None if base["deferrable"] is None else jnp.asarray(
            np.stack([p["deferrable"] for p in perturbed]))),
    )

    hosts_a = jnp.asarray(hosts, jnp.int32)
    cores_a = jnp.asarray(cores, jnp.int32)
    # per-scenario depths are already range-checked at Scenario construction
    depths = [int(sc.backfill_depth) for sc in scenarios]
    mb = max(depths) if max_backfill is None else int(max_backfill)
    if not 0 <= mb <= 31:
        raise ValueError(
            f"max_backfill must be in [0, 31] (uint32 skip-mask width), "
            f"got {mb}")
    if max(depths) > mb:
        raise ValueError(
            f"scenario wants backfill_depth {max(depths)} > "
            f"max_backfill={mb}")
    peak = jnp.asarray(
        [dataclasses.replace(dc, num_hosts=h, cores_per_host=c).peak_tflops
         for h, c in zip(hosts, cores)], jnp.float32)
    cap = jnp.asarray(
        [sc.power_cap_w if sc.power_cap_w is not None else math.inf
         for sc in scenarios], jnp.float32)
    carbon_base = jnp.asarray(
        [sc.carbon_cap_base_w if sc.carbon_cap_base_w is not None
         else math.inf for sc in scenarios], jnp.float32)
    carbon_slope = jnp.asarray(
        [sc.carbon_cap_slope for sc in scenarios], jnp.float32)

    # failure axis: dense [S, mh] window arrays with never-fail sentinels.
    # fault.py is imported locally — it reaches repro.core via the
    # checkpoint layer, and a module-level import here would close an
    # import cycle through repro.core.__init__ (same pattern as
    # scenario_mesh's local sharding import).
    from repro.runtime.fault import failure_arrays

    any_fail = any(sc.failures for sc in scenarios)
    if has_failures is None:
        has_failures = any_fail
    elif any_fail and not has_failures:
        raise ValueError(
            "has_failures=False but scenario(s) carry failure windows")
    fs_rows, fe_rows, fk_rows = [], [], []
    for sc, h in zip(scenarios, hosts):
        for f in sc.failures:
            if f.host >= h:
                raise ValueError(
                    f"scenario {sc.name!r}: failure host {f.host} out of "
                    f"range for its {h}-host topology")
        fs, fe, fk = failure_arrays(sc.failures, mh)
        fs_rows.append(fs)
        fe_rows.append(fe)
        fk_rows.append(fk)

    # dynamic-PUE axis: per-scenario model params with identity sentinels
    # (base 1.0, coeffs 0) on lanes that leave it off.
    any_pue = any(sc.pue_base is not None for sc in scenarios)
    if pue_on is None:
        pue_on = any_pue
    elif any_pue and not pue_on:
        raise ValueError("pue_on=False but scenario(s) set pue_base")
    pue_base = jnp.asarray(
        [1.0 if sc.pue_base is None else sc.pue_base for sc in scenarios],
        jnp.float32)
    pue_amb_coeff = jnp.asarray(
        [sc.pue_amb_coeff for sc in scenarios], jnp.float32)
    pue_amb_ref = jnp.asarray(
        [sc.pue_amb_ref for sc in scenarios], jnp.float32)
    pue_load_coeff = jnp.asarray(
        [sc.pue_load_coeff for sc in scenarios], jnp.float32)

    return ScenarioSet(
        workload=wl,
        host_mask_s=host_mask(hosts_a, mh),
        num_hosts=hosts_a,
        cores_per_host=cores_a,
        policy_id=jnp.asarray([resolve_policy(sc.policy) for sc in scenarios],
                              jnp.int32),
        backfill_depth=jnp.asarray(depths, jnp.int32),
        params=_per_host_params(base_params, scenarios, hosts, mh),
        power_cap_w=cap,
        carbon_cap_base_w=carbon_base,
        carbon_cap_slope=carbon_slope,
        shift_bins=jnp.asarray([int(sc.shift_bins) for sc in scenarios],
                               jnp.int32),
        peak_tflops=peak,
        fail_start=jnp.asarray(np.stack(fs_rows)),
        fail_end=jnp.asarray(np.stack(fe_rows)),
        fail_kill=jnp.asarray(np.stack(fk_rows)),
        pue_base=pue_base,
        pue_amb_coeff=pue_amb_coeff,
        pue_amb_ref=pue_amb_ref,
        pue_load_coeff=pue_load_coeff,
        names=names,
        max_backfill=mb,
        has_failures=bool(has_failures),
        pue_on=bool(pue_on),
    )


def _predict_masked(u_th: Array, params: PowerParams, mask: Array,
                    peak_tflops: Array, model: str,
                    cap_t: Array, intensity: Array | None,
                    *,
                    online_th: Array | None = None,
                    pue=None,
                    ambient: Array | None = None,
                    price: Array | None = None) -> Prediction:
    """Mask-aware :func:`repro.core.desim.predict_metrics` for one scenario.

    Padded (inactive) hosts must not dilute mean utilization or draw idle
    power, so both aggregations respect the active-host mask.

    Power-cap **enforcement** (vs. the old flag-only behavior): ``cap_t``
    (scalar or ``[T]``; +inf = uncapped) clips the *delivered* power, and
    performance metrics lose the same fraction of the active (above-idle)
    draw — a linear-throttle (DVFS-proxy) approximation.  Pre-cap demand is
    preserved in ``Prediction.power_demand_w`` so cap-violation analysis
    still sees what the workload *wanted*.  An uncapped scenario
    (``cap_t = +inf``) stays bit-for-bit the pre-enforcement output:
    ``min(x, inf) == x`` and the throttle select falls through to the raw
    utilization.

    New-axis hooks (all default off, leaving the body above unchanged):

    ``online_th`` (``[T, H]`` bool)
        Time-varying host availability from the failure axis — hosts in an
        *outage* window draw no power (not even idle) and drop out of the
        utilization denominator.  Degraded (drain) hosts stay online here.
    ``pue`` / ``ambient``
        Dynamic cooling: per-bin PUE from the **unthrottled** mean
        utilization and the °C trace (:func:`repro.traces.thermal.dynamic_pue`).
        Demand, cap enforcement, the idle floor, energy, gCO2 and cost all
        move to *facility* watts — the cap constrains what the meter sees.
    ``price`` (``[T]`` $/kWh)
        Fills ``energy_cost`` from delivered (facility) energy.
    """
    maskf = mask.astype(u_th.dtype)
    if online_th is None:
        it_demand = datacenter_power(u_th, params, model=model,
                                     online_mask=maskf)
        idle_floor = jnp.sum(jnp.asarray(params.p_idle, u_th.dtype) * maskf)
        util_raw = jnp.sum(u_th * maskf, axis=-1) / jnp.maximum(
            jnp.sum(maskf), 1.0)
    else:
        onf = online_th.astype(u_th.dtype) * maskf               # [T, H]
        it_demand = datacenter_power(u_th, params, model=model,
                                     online_mask=onf)
        # per-bin idle floor and utilization denominator: offline hosts
        # contribute neither idle watts nor zero-util dilution
        idle_floor = jnp.sum(
            jnp.asarray(params.p_idle, u_th.dtype) * onf, axis=-1)
        util_raw = jnp.sum(u_th * onf, axis=-1) / jnp.maximum(
            jnp.sum(onf, axis=-1), 1.0)
    pue_t = None
    demand = it_demand
    if pue is not None:
        from repro.traces.thermal import dynamic_pue
        pue_t = dynamic_pue(util_raw, ambient, pue)
        demand = it_demand * pue_t
        idle_floor = idle_floor * pue_t
    exceeded = demand > cap_t
    power = jnp.minimum(demand, cap_t)
    throttle = jnp.clip(
        (cap_t - idle_floor) / jnp.maximum(demand - idle_floor, 1e-9),
        0.0, 1.0)
    e = energy_kwh(power, SAMPLE_SECONDS)
    util = jnp.where(exceeded, util_raw * throttle, util_raw)
    tflops = util * peak_tflops
    eff = tflops / jnp.maximum(e, 1e-9)
    gco2 = None if intensity is None else carbon_gco2(e, intensity)
    cost = None if price is None else e * jnp.asarray(price, e.dtype)
    return Prediction(power_w=power, energy_kwh=e, tflops=tflops,
                      utilization=util, efficiency=eff, gco2=gco2,
                      power_demand_w=demand, pue=pue_t, energy_cost=cost)


def _scenario_lanes(
    ss: ScenarioSet,
    carbon_intensity: Array | None,
    ambient_c: Array | None,
    price: Array | None,
    *,
    max_hosts: int,
    t_bins: int,
    max_starts_per_bin: int,
    model: str,
    chunk: bool,
    use_pallas: bool = False,
    precision: str = "f32",
) -> tuple[SimOutput, Prediction]:
    """vmap of the per-lane DES + prediction — the shared trace-level body.

    Both execution paths run exactly this: the single-device path vmaps it
    over the full S axis, the sharded path runs it per device over the local
    S shard (``chunk`` is resolved from the *global* batch in both cases, so
    every lane compiles the same readout program and the two paths agree bit
    for bit).  The ``[t_bins]`` traces (carbon, ambient, price) are shared
    closure constants under the vmap; everything per-scenario rides the S
    axis, and the static ``has_failures``/``pue_on`` aux flags decide
    whether the failure/PUE machinery is compiled in at all.

    ``use_pallas`` swaps the unfused readout (:func:`_predict_masked`) for
    the fused kernel (:mod:`repro.kernels.des_readout` — interpret mode off
    TPU), which rebuilds the per-bin online mask in-kernel instead of
    materializing the ``[T, H]`` availability tensor; ``precision`` is its
    bf16-where-tolerable policy knob.  The kernel path is within the
    ``tests/reference.py`` oracle tolerance of the unfused one but not
    bitwise (padded-lane summation), so it is opt-in per call.
    """
    if use_pallas:
        from repro.kernels.ops import des_readout
        # tracecheck: disable=TC007 — platform dispatch at trace time
        pallas_backend = ("pallas" if jax.devices()[0].platform == "tpu"
                          else "pallas_interpret")

    def one(w, mask, cores, policy_id, backfill_depth, params,
            cap_w, carbon_base, carbon_slope, peak,
            fail_start, fail_end, fail_kill,
            pue_base, pue_amb_coeff, pue_amb_ref, pue_load_coeff):
        use_fail = ss.has_failures
        sim = simulate_utilization_masked(
            w, mask, cores,
            max_hosts=max_hosts, t_bins=t_bins,
            max_starts_per_bin=max_starts_per_bin,
            policy_id=policy_id, backfill_depth=backfill_depth,
            max_backfill=ss.max_backfill,   # static aux, uniform over S
            force_chunked_readout=chunk,
            fail_start=fail_start if use_fail else None,
            fail_end=fail_end if use_fail else None,
            fail_kill=fail_kill if use_fail else None,
        )
        with jax.named_scope("opendt.power_readout"):
            # effective per-bin cap: min(static facility cap, carbon-aware
            # cap).  The intensity trace is shared across scenarios (closure
            # constant under the vmap); only the scalar cap parameters ride
            # the S axis, so (caps x shifts x topologies) grids stay one
            # program.
            cap_t = cap_w
            if carbon_intensity is not None:
                cap_t = jnp.minimum(
                    cap_t,
                    jnp.maximum(carbon_base + carbon_slope * carbon_intensity,
                                0.0))
            if use_pallas:
                # fused readout: failure windows become kernel operands (the
                # online mask is rebuilt per tile from iota time ids) and the
                # identity-PUE sentinels make the PUE multiply an exact no-op
                # on lanes that leave the axis off.
                rd = des_readout(
                    sim.u_th, backend=pallas_backend,
                    p_idle=params.p_idle, p_max=params.p_max, r=params.r,
                    mask=mask, cap_t=cap_t, intensity=carbon_intensity,
                    ambient=ambient_c, price=price, peak_tflops=peak,
                    pue_base=pue_base, pue_amb_coeff=pue_amb_coeff,
                    pue_amb_ref=pue_amb_ref, pue_load_coeff=pue_load_coeff,
                    fail_start=fail_start if use_fail else None,
                    fail_end=fail_end if use_fail else None,
                    fail_kill=fail_kill if use_fail else None,
                    model=model, precision=precision,
                    dt_seconds=SAMPLE_SECONDS)
                pred = Prediction(
                    power_w=rd["power_w"], energy_kwh=rd["energy_kwh"],
                    tflops=rd["tflops"], utilization=rd["utilization"],
                    efficiency=rd["efficiency"],
                    gco2=None if carbon_intensity is None else rd["gco2"],
                    power_demand_w=rd["power_demand_w"],
                    pue=rd["pue"] if ss.pue_on else None,
                    energy_cost=None if price is None else rd["energy_cost"])
                return sim, pred
            online_th = None
            if use_fail:
                # power-side availability: only *outage* hosts stop drawing
                # power during their window (degraded hosts drain but burn)
                tt = jnp.arange(t_bins, dtype=jnp.int32)[:, None]     # [T, 1]
                offline = (fail_kill[None, :] & (tt >= fail_start[None, :])
                           & (tt < fail_end[None, :]))                # [T, H]
                online_th = mask[None, :] & jnp.logical_not(offline)
            pue = None
            if ss.pue_on:
                from repro.traces.thermal import PUEParams
                pue = PUEParams(base=pue_base, amb_coeff=pue_amb_coeff,
                                amb_ref=pue_amb_ref, load_coeff=pue_load_coeff)
            pred = _predict_masked(sim.u_th, params, mask, peak, model,
                                   cap_t, carbon_intensity,
                                   online_th=online_th, pue=pue,
                                   ambient=ambient_c, price=price)
            return sim, pred

    return jax.vmap(one)(ss.workload, ss.host_mask_s, ss.cores_per_host,
                         ss.policy_id, ss.backfill_depth, ss.params,
                         ss.power_cap_w, ss.carbon_cap_base_w,
                         ss.carbon_cap_slope, ss.peak_tflops,
                         ss.fail_start, ss.fail_end, ss.fail_kill,
                         ss.pue_base, ss.pue_amb_coeff, ss.pue_amb_ref,
                         ss.pue_load_coeff)


def _run_scenarios_body(
    ss: ScenarioSet,
    carbon_intensity: Array | None,
    ambient_c: Array | None,
    price: Array | None,
    *,
    max_hosts: int,
    t_bins: int,
    max_starts_per_bin: int,
    model: str,
    use_pallas: bool,
    precision: str,
) -> tuple[SimOutput, Prediction]:
    # the DES core's own readout bound is per-scenario; under the scenario
    # vmap every intermediate gains the S axis, so the bound must include S
    # (workload leaves are [S, J]: take J from the trailing axis).
    n_jobs = int(ss.workload.submit_bin.shape[-1])
    chunk = ss.num_scenarios * n_jobs * t_bins > _BATCH_READOUT_THRESHOLD
    return _scenario_lanes(
        ss, carbon_intensity, ambient_c, price,
        max_hosts=max_hosts, t_bins=t_bins,
        max_starts_per_bin=max_starts_per_bin, model=model, chunk=chunk,
        use_pallas=use_pallas, precision=precision)


_RUN_STATICS = ("max_hosts", "t_bins", "max_starts_per_bin", "model",
                "use_pallas", "precision")
_run_scenarios_jit = jax.jit(_run_scenarios_body,
                             static_argnames=_RUN_STATICS)
#: same program, but the ScenarioSet argument's buffers are donated — the
#: optimizer's generation carry uses this so warm searches stop
#: double-buffering the [S, J] workload leaves.  A separate compiled
#: program, hence a separate cache: run_scenarios._cache_size sums both.
_run_scenarios_jit_donated = jax.jit(_run_scenarios_body,
                                     static_argnames=_RUN_STATICS,
                                     donate_argnums=(0,))


#: mesh axis name the scenario batch is sharded over
SCENARIO_AXIS = "scenarios"


def scenario_mesh(num_devices: int | None = None):
    """A 1-D device mesh over ``SCENARIO_AXIS`` (default: all local devices).

    On CPU-only deployments, export
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* process
    start to split the host into N devices (the ``tier1-multidevice`` CI job
    runs the equivalence suite exactly that way).
    """
    devs = jax.devices()  # tracecheck: disable=TC007 — mesh discovery is this helper's purpose
    n = len(devs) if num_devices is None else int(num_devices)
    return jax.make_mesh((n,), (SCENARIO_AXIS,),
                         (jax.sharding.AxisType.Auto,), devices=devs[:n])


@functools.partial(jax.jit, static_argnames=("mesh", "max_hosts", "t_bins",
                                             "max_starts_per_bin", "model",
                                             "chunk", "use_pallas",
                                             "precision"))
def _run_scenarios_sharded_jit(
    ss: ScenarioSet,
    carbon_intensity: Array | None,
    ambient_c: Array | None,
    price: Array | None,
    *,
    mesh,
    max_hosts: int,
    t_bins: int,
    max_starts_per_bin: int,
    model: str,
    chunk: bool,
    use_pallas: bool = False,
    precision: str = "f32",
) -> tuple[SimOutput, Prediction]:
    from jax.sharding import PartitionSpec as P

    def body(ss_local: ScenarioSet, ci_local: Array | None,
             amb_local: Array | None, price_local: Array | None):
        return _scenario_lanes(
            ss_local, ci_local, amb_local, price_local,
            max_hosts=max_hosts, t_bins=t_bins,
            max_starts_per_bin=max_starts_per_bin, model=model, chunk=chunk,
            use_pallas=use_pallas, precision=precision)

    return jax.shard_map(
        body, mesh=mesh,
        # S-axis sharded; the [T] traces replicated on every device
        in_specs=(P(SCENARIO_AXIS), P(), P(), P()),
        out_specs=P(SCENARIO_AXIS),
        check_vma=False,
    )(ss, carbon_intensity, ambient_c, price)


def _pad_scenario_axis(ss: ScenarioSet, pad: int) -> ScenarioSet:
    """Pad the S axis by replicating lane 0 (masked off by the caller).

    Mirrors the host-axis padding story: the padded lanes are real
    (scenario-0 copies) so every device runs a full shard, and the caller
    slices the outputs back to the true S.
    """
    if pad == 0:
        return ss
    padded = jax.tree.map(
        lambda x: jnp.concatenate(
            [x, jnp.repeat(x[:1], pad, axis=0)], axis=0), ss)
    return dataclasses.replace(padded, names=ss.names + ("",) * pad)


def run_scenarios(
    ss: ScenarioSet,
    *,
    max_hosts: int,
    t_bins: int,
    max_starts_per_bin: int = 64,
    model: str = "opendc",
    carbon_intensity: "Array | np.ndarray | None" = None,
    ambient_c: "Array | np.ndarray | None" = None,
    price: "Array | np.ndarray | None" = None,
    shard: bool = False,
    mesh=None,
    use_pallas: bool = False,
    readout_precision: str = "f32",
    donate: bool = False,
) -> tuple[SimOutput, Prediction]:
    """Simulate + predict all S scenarios in one jitted program.

    Returns a batched :class:`SimOutput` and :class:`Prediction` whose array
    leaves lead with the scenario axis: ``sim.u_th`` is
    ``[S, t_bins, max_hosts]`` (padded hosts read 0), ``sim.job_start`` /
    ``sim.job_host`` are ``[S, J]`` (-1 = never started), and every
    :class:`~repro.core.desim.Prediction` leaf is ``[S, t_bins]``.

    ``carbon_intensity`` (``[t_bins]`` gCO2/kWh, shared by all scenarios —
    see :mod:`repro.traces.carbon`) activates the carbon subsystem: the
    prediction gains per-bin ``gco2`` and carbon-aware power caps
    (``Scenario.carbon_cap_base_w``) become computable.  Omitting it keeps
    every output leaf bit-for-bit identical to the pre-carbon engine
    (``gco2=None``); scenarios that *request* a carbon-aware cap without a
    trace are rejected loudly rather than silently uncapped.

    ``ambient_c`` (``[t_bins]`` °C, see :mod:`repro.traces.thermal`) feeds
    the dynamic-PUE axis of lanes that set ``Scenario.pue_base``; lanes
    whose ``pue_amb_coeff`` is nonzero *require* it (rejected loudly,
    mirroring the carbon-cap rule).  ``price`` (``[t_bins]`` $/kWh, see
    :mod:`repro.traces.price`) fills ``Prediction.energy_cost`` for every
    lane from delivered (facility) energy.  Failure windows
    (``Scenario.failures``) need no trace but must *start* inside the
    horizon — a window opening at or past ``t_bins`` can never fire and is
    rejected as a mis-specified what-if.

    One compilation covers any scenario batch with the same
    ``(S, max_hosts, t_bins, J, max_backfill)`` shape (per intensity
    presence) — the sequential what-if loop's per-candidate
    retrace/recompile is gone, and because the placement policy, caps and
    time shifts are traced ``[S]`` axes (or same-shape workload data),
    scheduler/carbon sweeps ride the same program as topology sweeps.
    Scenario *names* are pytree aux data (part of the jit cache key), so
    they are anonymized before entering jit — differently-named sweeps of
    the same shape share one compilation.

    **Scenario-axis sharding**: with ``shard=True`` the S axis is
    ``shard_map``-ped over the devices of ``mesh`` (default: a 1-D
    :func:`scenario_mesh` over all local devices) — each device runs the
    *same* per-lane program over its local shard, so 100s-of-candidate
    sweeps scale across cores/chips while staying **bit-for-bit identical**
    to the single-device vmap path (pinned by
    ``tests/test_shard_scenarios.py``; speedup recorded by
    ``benchmarks/whatif_batch.py``).  S is padded to a multiple of the
    device count with masked scenario-0 replicas and the outputs are sliced
    back to the true S, mirroring the host-axis padding story.

    **Fused readout** (``use_pallas=True``): the post-scan readout runs as
    the one-pass :mod:`repro.kernels.des_readout` kernel (Pallas on TPU,
    interpret mode elsewhere) instead of the unfused XLA pipeline.
    Outputs stay inside the ``tests/reference.py`` oracle tolerance but
    are *not* bitwise vs the default readout (padded-lane summation), so
    the flag defaults off and golden comparisons keep the legacy path.
    ``readout_precision="bf16"`` additionally computes the derived
    performance leaves (tflops, efficiency) in bf16 — sustainability
    leaves stay f32; pinned by ``tests/golden/readout_bf16.npz``.

    **Donation** (``donate=True``, single-device path only): the
    ``ScenarioSet``'s array buffers are donated to the compiled program,
    halving peak residency of the dominant ``[S, J]`` workload leaves on
    warm calls.  The caller's ``ss`` (its leaves, including any aliases)
    is **invalidated** — snapshot anything still needed first.  The
    optimizer's generation loop runs this way (it re-builds ``ss`` every
    generation); it is a separate compiled program from the non-donating
    one, and ``run_scenarios._cache_size`` counts both.
    """
    if carbon_intensity is None:
        if np.isfinite(np.asarray(ss.carbon_cap_base_w)).any():
            raise ValueError(
                "scenario(s) set carbon_cap_base_w but no carbon_intensity "
                "trace was supplied — a carbon-aware cap cannot be computed "
                "without one (pass carbon_intensity=[t_bins] gCO2/kWh)")
        ci = None
    else:
        ci = jnp.asarray(
            validate_carbon_intensity(np.asarray(carbon_intensity), t_bins),
            jnp.float32)
    if ss.has_failures:
        fs = np.asarray(ss.fail_start)
        bad = (fs < np.iinfo(np.int32).max) & (fs >= t_bins)
        if bad.any():
            s_bad, h_bad = map(int, np.argwhere(bad)[0])
            raise ValueError(
                f"scenario {s_bad} host {h_bad}: failure window starts at "
                f"bin {int(fs[s_bad, h_bad])}, at/past the {t_bins}-bin "
                "horizon — it can never fire")
    if ambient_c is None:
        if ss.pue_on and np.asarray(ss.pue_amb_coeff).any():
            raise ValueError(
                "scenario(s) set pue_amb_coeff but no ambient_c trace was "
                "supplied — the ambient-driven PUE term cannot be computed "
                "without one (pass ambient_c=[t_bins] °C)")
        amb = None
    else:
        from repro.traces.thermal import validate_ambient
        amb = jnp.asarray(
            validate_ambient(np.asarray(ambient_c), t_bins), jnp.float32)
    if price is None:
        pr = None
    else:
        from repro.traces.price import validate_price
        pr = jnp.asarray(
            validate_price(np.asarray(price), t_bins), jnp.float32)
    s = ss.num_scenarios
    anon = dataclasses.replace(ss, names=("",) * s)
    if not shard:
        run = _run_scenarios_jit_donated if donate else _run_scenarios_jit
        with warnings.catch_warnings():
            # expected on the donated program: the small [S] knob leaves
            # have no same-shaped output to reuse, and jax reports them.
            # The [S, J] workload leaves — the residency that matters —
            # do get reused; tests/test_compile_invariants.py asserts it.
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return run(
                anon, ci, amb, pr, max_hosts=max_hosts, t_bins=t_bins,
                max_starts_per_bin=max_starts_per_bin, model=model,
                use_pallas=use_pallas, precision=readout_precision,
            )
    mesh = scenario_mesh() if mesh is None else mesh
    n_dev = mesh.shape[SCENARIO_AXIS]
    per_dev = -(-s // n_dev)
    if n_dev > 1:
        # keep >= 2 lanes per device: on jax 0.9.0 a batch-1 vmap compiles
        # to a different program than the batch-S one, and with carbon and
        # price traces its gco2 / energy_cost leaves differ from the vmap
        # path's by 1 ulp — one masked replica lane keeps the bitwise gate.
        per_dev = max(per_dev, 2)
    padded = _pad_scenario_axis(anon, per_dev * n_dev - s)
    # readout chunking is resolved from the *global* (unpadded) batch so the
    # per-lane program matches the vmap path's exactly (bit-for-bit gate).
    n_jobs = int(ss.workload.submit_bin.shape[-1])
    chunk = s * n_jobs * t_bins > _BATCH_READOUT_THRESHOLD
    out = _run_scenarios_sharded_jit(
        padded, ci, amb, pr, mesh=mesh, max_hosts=max_hosts, t_bins=t_bins,
        max_starts_per_bin=max_starts_per_bin, model=model, chunk=chunk,
        use_pallas=use_pallas, precision=readout_precision,
    )
    return jax.tree.map(lambda x: x[:s], out)


# surfaced for the single-compilation regression tests; `_cache_size` is
# private jax API, so its absence must degrade to None, not an import
# error.  The donated program is a distinct executable with its own cache,
# so the counter sums both: a donated-only workload (the optimizer) and a
# non-donating one (the grid benchmarks) each still count 1.
_jit_caches = tuple(
    getattr(f, "_cache_size", None)
    for f in (_run_scenarios_jit, _run_scenarios_jit_donated))
run_scenarios._cache_size = (
    (lambda: sum(c() for c in _jit_caches)) if all(_jit_caches) else None)


@dataclasses.dataclass(frozen=True)
class ScenarioSummary:
    """Host-side per-scenario read-out an operator (or the HITL gate) compares.

    Scheduler provenance and outcome travel together: ``policy`` /
    ``backfill_depth`` identify the placement policy the scenario ran,
    ``mean_wait_bins`` / ``p99_wait_bins`` are queue-wait statistics
    (``job_start - submit`` in 5-minute bins, over jobs that started; NaN if
    nothing started) and ``unplaced_jobs`` counts valid jobs that never
    started inside the horizon — the fields
    :func:`repro.core.feedback.propose_from_scenario` needs to recommend a
    scheduler change on wait/placement grounds against an energy budget.

    ``kwh_per_cpu_hour`` is NaN when the scenario's workload has zero CPU-hours
    — an empty trace is surfaced, never hidden behind a clamped denominator.

    Sustainability fields: ``gco2`` is the scenario's total operational
    carbon (grams CO2; NaN when no carbon-intensity trace was supplied) and
    ``carbon_intensity_avg`` the energy-weighted mean grid intensity it ran
    against (gCO2/kWh; NaN without a trace or with zero energy).  Cap
    fields reflect *enforcement*: ``energy_kwh``/``mean_power_w``/
    ``peak_power_w`` are delivered (post-cap), ``peak_demand_w`` is what the
    workload wanted, and ``cap_exceeded_bins`` counts bins where demand ran
    into the effective (static ∧ carbon-aware) cap.  ``shift_bins`` records
    the applied deferrable-job time shift.

    New-axis fields (``None``/0 when the axis is off — ``None`` rather
    than NaN so dataclass equality keeps working in the shard-equivalence
    tests): ``mean_pue`` is the energy-unweighted mean dynamic PUE,
    ``energy_cost`` the total electricity cost ($, against the spot-price
    trace; power fields are *facility*-level when PUE is on) and
    ``failure_events`` the number of failure windows the scenario injects.
    """

    name: str
    num_hosts: int
    cores_per_host: int
    policy: str
    backfill_depth: int
    mean_util: float
    p99_queue: float
    max_queue: int
    mean_wait_bins: float
    p99_wait_bins: float
    unplaced_jobs: int
    total_jobs: int
    energy_kwh: float
    mean_power_w: float
    peak_power_w: float
    peak_demand_w: float
    cpu_hours: float
    kwh_per_cpu_hour: float
    gco2: float
    carbon_intensity_avg: float
    shift_bins: int
    power_cap_w: float | None
    carbon_cap_base_w: float | None
    carbon_cap_slope: float
    cap_exceeded_bins: int
    mean_pue: float | None = None
    energy_cost: float | None = None
    failure_events: int = 0


def summarize_scenarios(
    ss: ScenarioSet, sim: SimOutput, pred: Prediction,
    carbon_intensity: "np.ndarray | Array | None" = None,
) -> list[ScenarioSummary]:
    """Collapse batched outputs into one comparable record per scenario.

    Pass the same ``carbon_intensity`` the sweep ran with so cap-violation
    counting sees the effective (carbon-aware) per-bin cap; carbon totals
    come from ``pred.gco2`` directly.
    """
    util = np.asarray(pred.utilization)        # [S, T] (mask-aware)
    queue = np.asarray(sim.queue_len)          # [S, T]
    start = np.asarray(sim.job_start)          # [S, J]
    submit = np.asarray(ss.workload.submit_bin)  # [S, J] (post-perturbation)
    valid = np.asarray(ss.workload.valid)      # [S, J]
    power = np.asarray(pred.power_w)           # [S, T] delivered (post-cap)
    demand = (np.asarray(pred.power_demand_w)  # [S, T] pre-cap demand
              if pred.power_demand_w is not None else power)
    energy = np.asarray(pred.energy_kwh)       # [S, T]
    gco2 = (np.asarray(pred.gco2)              # [S, T] or None
            if pred.gco2 is not None else None)
    cap = np.asarray(ss.power_cap_w)           # [S]
    cbase = np.asarray(ss.carbon_cap_base_w)   # [S]
    cslope = np.asarray(ss.carbon_cap_slope)   # [S]
    shifts = np.asarray(ss.shift_bins)         # [S]
    policy = np.asarray(ss.policy_id)          # [S]
    depth = np.asarray(ss.backfill_depth)      # [S]
    pue = (np.asarray(pred.pue)                # [S, T] or None
           if pred.pue is not None else None)
    cost = (np.asarray(pred.energy_cost, np.float64)  # [S, T] or None
            if pred.energy_cost is not None else None)
    fail_ct = (np.asarray(ss.fail_start)       # [S] windows per scenario
               < np.iinfo(np.int32).max).sum(axis=-1)
    ci = (None if carbon_intensity is None
          else np.asarray(carbon_intensity, np.float64))
    cpu_h = np.asarray(
        jax.vmap(lambda w: jnp.sum(w.cpu_hours()))(ss.workload))

    out = []
    for s, name in enumerate(ss.names):
        ch = float(cpu_h[s])
        ekwh = float(energy[s].sum())
        placed = (start[s] >= 0) & valid[s]
        waits = (start[s] - submit[s])[placed]
        cap_t = np.full_like(power[s], cap[s])     # effective per-bin cap
        if ci is not None:
            cap_t = np.minimum(
                cap_t, np.maximum(cbase[s] + cslope[s] * ci, 0.0))
        g = float(gco2[s].sum()) if gco2 is not None else float("nan")
        out.append(ScenarioSummary(
            name=name,
            num_hosts=int(ss.num_hosts[s]),
            cores_per_host=int(ss.cores_per_host[s]),
            policy=POLICY_NAMES[int(policy[s])],
            backfill_depth=int(depth[s]),
            mean_wait_bins=(float(waits.mean()) if waits.size
                            else float("nan")),
            p99_wait_bins=(float(np.percentile(waits, 99)) if waits.size
                           else float("nan")),
            mean_util=float(util[s].mean()),
            p99_queue=float(np.percentile(queue[s], 99)),
            max_queue=int(queue[s].max()),
            unplaced_jobs=int(((start[s] < 0) & valid[s]).sum()),
            total_jobs=int(valid[s].sum()),
            energy_kwh=ekwh,
            mean_power_w=float(power[s].mean()),
            peak_power_w=float(power[s].max()),
            peak_demand_w=float(demand[s].max()),
            cpu_hours=ch,
            kwh_per_cpu_hour=(ekwh / ch) if ch > 0 else float("nan"),
            gco2=g,
            carbon_intensity_avg=(g / ekwh if np.isfinite(g) and ekwh > 0
                                  else float("nan")),
            shift_bins=int(shifts[s]),
            power_cap_w=None if np.isinf(cap[s]) else float(cap[s]),
            carbon_cap_base_w=(None if np.isinf(cbase[s])
                               else float(cbase[s])),
            carbon_cap_slope=float(cslope[s]),
            cap_exceeded_bins=int((demand[s] > cap_t).sum()),
            mean_pue=(float(pue[s].mean()) if pue is not None else None),
            energy_cost=(float(cost[s].sum()) if cost is not None else None),
            failure_events=int(fail_ct[s]),
        ))
    return out


def evaluate_scenarios(
    workload: Workload,
    dc: DatacenterConfig,
    scenarios: "list[Scenario] | tuple[Scenario, ...]",
    *,
    t_bins: int,
    base_params: PowerParams = PowerParams(),
    max_hosts: int | None = None,
    model: str = "opendc",
    max_starts_per_bin: int = 64,
    carbon_intensity: "Array | np.ndarray | None" = None,
    ambient_c: "Array | np.ndarray | None" = None,
    price: "Array | np.ndarray | None" = None,
    shard: bool = False,
    mesh=None,
    use_pallas: bool = False,
) -> tuple[ScenarioSet, SimOutput, Prediction, list[ScenarioSummary]]:
    """End-to-end what-if sweep: build, batch-simulate, summarize.

    Convenience wrapper over :func:`build_scenario_set` ->
    :func:`run_scenarios` -> :func:`summarize_scenarios`; returns all four
    artifacts (the device-side batch plus host-side summaries) so callers
    can both rank candidates and drill into per-bin fields.  ``scenarios``
    may sweep any :class:`Scenario` axis — topology, placement policy,
    backfill depth, power model, enforced (carbon-aware) caps, workload
    scaling and time-shifting — and the whole sweep still compiles once per
    ``(S, max_hosts, t_bins, J, max_backfill)`` shape.  Supplying
    ``carbon_intensity`` ([t_bins] gCO2/kWh) fills the ``gco2`` /
    ``carbon_intensity_avg`` summary fields; without it they are NaN and
    outputs match the pre-carbon engine bit for bit.
    """
    ss = build_scenario_set(workload, dc, scenarios, base_params,
                            max_hosts=max_hosts)
    sim, pred = run_scenarios(
        ss, max_hosts=ss.max_hosts, t_bins=t_bins,
        max_starts_per_bin=max_starts_per_bin, model=model,
        carbon_intensity=carbon_intensity, ambient_c=ambient_c, price=price,
        shard=shard, mesh=mesh, use_pallas=use_pallas,
    )
    return ss, sim, pred, summarize_scenarios(
        ss, sim, pred, carbon_intensity=carbon_intensity)
