"""DigitalTwin facade — the whole OpenDT loop in one object.

Wires the physical-twin telemetry source, the Orchestrator (windows,
pipelined simulate/calibrate), the SLO monitor and the HITL gate into the
closed cycle of Figure 1:  telemetry -> twin -> (simulate + calibrate) ->
SLO-aware feedback -> human-in-the-loop.

The physical twin that ships with the repo is ``TraceGroundTruth``: it
replays a workload trace with synthesized hidden-model telemetry
(experiments E1/E2).  Any ``telemetry_source(window, bins)`` that returns
measured ``TelemetryWindow``s can stand in for it.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.desim import simulate_utilization
from repro.core.feedback import HITLGate, Proposal
from repro.core.orchestrator import Orchestrator, OrchestratorConfig, WindowRecord
from repro.core.power import PowerParams
from repro.core.slo import SLOReport
from repro.core.state import TwinState, WindowOutput, twin_step
from repro.core.telemetry import TelemetryWindow, clip_to_window

# NOTE: repro.traces.* is imported lazily inside functions — traces depends on
# repro.core.power, and importing it at module scope would close a cycle
# through the repro.core package __init__.


class TraceGroundTruth:
    """Physical-twin stand-in: hidden-model telemetry over a trace replay."""

    def __init__(self, workload, dc, t_bins: int, gt=None):
        from repro.traces.surf import GroundTruthSpec, synthesize_ground_truth
        gt = gt or GroundTruthSpec()
        sim = simulate_utilization(
            workload, num_hosts=dc.num_hosts,
            cores_per_host=dc.cores_per_host, t_bins=t_bins,
        )
        self.u_th = np.asarray(sim.u_th)
        self.power = synthesize_ground_truth(self.u_th, gt)

    def window(self, idx: int, bins_per_window: int) -> TelemetryWindow:
        return clip_to_window(
            idx, bins_per_window, 0, self.u_th, self.power
        )


@dataclasses.dataclass
class TwinRunResult:
    records: list[WindowRecord]
    overall_mape: float
    per_window_mape: np.ndarray
    slo_reports: list[SLOReport]
    under_estimation_fraction: float
    approved_proposals: list[Proposal]


class DigitalTwin:
    """OpenDT's outer loop.

    Profiler spans: ``opendt.twin.init`` (construction), ``opendt.ingest``
    (per window: the telemetry source and the store's ingest) and
    ``opendt.twin.finish`` (the run's reports), beside the orchestrator's
    ``opendt.window`` spans.
    """

    def __init__(
        self,
        workload,
        dc,
        t_bins: int,
        cfg: OrchestratorConfig = OrchestratorConfig(),
        base_params: PowerParams = PowerParams(),
        hitl_policy: Callable[[Proposal], bool | None] | None = None,
    ):
        with TraceAnnotation("opendt.twin.init"):
            self.gate = HITLGate(policy=hitl_policy)
            self.orchestrator = Orchestrator(
                workload, dc, t_bins, cfg, base_params, gate=self.gate,
            )

    def run(
        self,
        telemetry_source: Callable[[int, int], TelemetryWindow],
        num_windows: int | None = None,
    ) -> TwinRunResult:
        """Run the closed loop: per window, ingest telemetry then twin it."""
        orch = self.orchestrator
        n = num_windows if num_windows is not None else orch.num_windows
        approved: list[Proposal] = []
        for w in range(n):
            with TraceAnnotation("opendt.ingest", window=w,
                                 twin=orch.trace_id):
                tw = telemetry_source(w, orch.cfg.bins_per_window)
                orch.store.ingest(tw)
            orch.run_window(w)
            approved.extend(self.gate.drain())
        with TraceAnnotation("opendt.twin.finish", twin=orch.trace_id):
            return TwinRunResult(
                records=orch.records,
                overall_mape=orch.overall_mape(),
                per_window_mape=orch.per_window_mape(),
                slo_reports=orch.monitor.report(),
                under_estimation_fraction=orch.bias.under_fraction,
                approved_proposals=approved,
            )


# -- fleet twinning: vmap(twin_step) over independent datacenters -------------

def _flatten_with_names(state: TwinState):
    """``[(field-qualified leaf name, leaf), ...]`` + treedef, for errors.

    ``TwinState`` (and ``PowerParams``) register plain pytree nodes without
    key paths, so names are built from the dataclass fields — the level an
    error message needs (``params.p_idle``, ``hist_u``, ``sim_u``).
    """
    out = []
    for f in dataclasses.fields(state):
        if f.name == "cfg":
            continue
        sub = getattr(state, f.name)
        if isinstance(sub, PowerParams):
            out.extend((f"{f.name}.{g.name}", getattr(sub, g.name))
                       for g in dataclasses.fields(sub))
        else:
            out.extend((f.name, x) for x in jax.tree_util.tree_leaves(sub))
    return out, jax.tree_util.tree_structure(state)


def stack_twin_states(states: "list[TwinState] | tuple[TwinState, ...]") -> TwinState:
    """Stack D independent twins into one batched ``TwinState`` ``[D, ...]``.

    Every state must share the same :class:`~repro.core.state.TwinConfig`
    *and* the same leaf shapes (both checked up front, so mismatched fleets
    fail loudly at stack time, naming the offending leaf and lane) — i.e.
    the fleet twins datacenters of one padded size per compiled program,
    like the scenario engine's ``max_hosts`` axis.
    """
    if not states:
        raise ValueError("need at least one TwinState to stack")
    cfg = states[0].cfg
    ref, ref_def = _flatten_with_names(states[0])
    for lane, s in enumerate(states[1:], start=1):
        if s.cfg != cfg:
            raise ValueError(
                "fleet states must share one TwinConfig (got differing "
                f"configs:\n  {cfg}\n  {s.cfg})")
        cur, cur_def = _flatten_with_names(s)
        if cur_def != ref_def:
            raise ValueError(
                f"fleet states must share one pytree structure; lane {lane} "
                "differs from lane 0 (a field present on one side only, "
                "e.g. sim_u)")
        for (name, a), (_, b) in zip(ref, cur):
            if jnp.shape(a) != jnp.shape(b):
                raise ValueError(
                    f"fleet states must share leaf shapes; leaf {name} has "
                    f"shape {jnp.shape(b)} in lane {lane} vs "
                    f"{jnp.shape(a)} in lane 0")
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *states)


def index_twin_state(fleet: TwinState, i: int) -> TwinState:
    """Extract one twin's state from a batched fleet state."""
    return jax.tree.map(lambda x: x[i], fleet)


def update_twin_state_lane(fleet: TwinState, i: int,
                           state: TwinState) -> TwinState:
    """Write one twin's state into lane ``i`` of a batched fleet state.

    The admission half of lane multiplexing (:mod:`repro.serve.batching`):
    a tenant joins a resident fleet by landing its ``TwinState`` on a free
    lane; :func:`index_twin_state` is the eviction half.  Host-side eager
    ops — admission/eviction are rare control-plane events, not per-step
    work — and config- and shape-checked like :func:`stack_twin_states`
    (a mismatched state names the offending leaf and lane instead of
    surfacing as a cryptic scatter error).
    """
    if state.cfg != fleet.cfg:
        raise ValueError(
            "lane state must share the fleet's TwinConfig (got differing "
            f"configs:\n  {fleet.cfg}\n  {state.cfg})")
    f_leaves, f_def = _flatten_with_names(fleet)
    s_leaves, s_def = _flatten_with_names(state)
    if s_def != f_def:
        raise ValueError(
            f"lane {i} state must share the fleet's pytree structure "
            "(a field present on one side only, e.g. sim_u)")
    for (name, f), (_, s) in zip(f_leaves, s_leaves):
        if jnp.shape(f)[1:] != jnp.shape(s):
            raise ValueError(
                f"lane {i} state leaf {name} has shape {jnp.shape(s)}; the "
                f"fleet carries {jnp.shape(f)} (want {jnp.shape(f)[1:]} "
                "per lane)")
    return jax.tree.map(lambda f, s: f.at[i].set(s), fleet, state)


#: one fused program that twins D datacenters for one window: every leaf of
#: the three inputs leads with the fleet axis [D, ...].
fleet_step = jax.jit(jax.vmap(twin_step))


def _fleet_step_masked(fleet: TwinState, telemetry, sim_slices, lane_active):
    """One fleet window with per-lane masking (partially-filled steps).

    ``lane_active`` is a ``[D]`` bool vector: active lanes advance exactly
    as :func:`fleet_step` would (each lane bitwise-identical to a solo
    ``twin_step`` — the pinned fleet invariant), inactive lanes carry their
    state through **unchanged** — window index, history, accumulators, all
    of it.  That is what lets a dynamic batcher pack any subset of resident
    tenants into a fixed-shape ``[D]`` call: empty lanes ride along on
    padding telemetry without their twins ever noticing, the same
    pad-and-mask trick the scenario engine plays on the S axis.

    Outputs are returned for every lane (inactive lanes produce padding
    predictions the caller must ignore — the batcher only reads active
    lanes).
    """
    stepped, outs = jax.vmap(twin_step)(fleet, telemetry, sim_slices)

    def keep(new, old):
        mask = lane_active.reshape(lane_active.shape + (1,) * (new.ndim - 1))
        return jax.numpy.where(mask, new, old)

    return jax.tree.map(keep, stepped, fleet), outs


# the fleet carry is donated like fleet_step's would be: callers rebind
# `fleet, outs = fleet_step_masked(fleet, ...)`, so the incoming lane
# buffers are reused in place batch after batch
_fleet_step_masked_jit = jax.jit(_fleet_step_masked, donate_argnums=(0,))


def fleet_step_masked(fleet: TwinState, telemetry, sim_slices, lane_active,
                      *, shard: bool = False, mesh=None
                      ) -> tuple[TwinState, WindowOutput]:
    """Advance a partially-filled fleet one window in ONE compiled program.

    The serving primitive behind :class:`repro.serve.service.TwinService`:
    every dynamic batch — whatever mix of tenants is ready — is one call to
    this one jitted program, so an arbitrary tenant arrival pattern never
    recompiles.  ``fleet`` leaves lead with ``[D, ...]``; ``telemetry`` /
    ``sim_slices`` are one window's
    :class:`~repro.core.state.TelemetrySlice` /
    :class:`~repro.core.state.SimSlice` with ``[D, ...]`` leaves;
    ``lane_active`` is the ``[D]`` bool fill mask.

    With ``shard=True`` the D axis is ``shard_map``-ped over ``mesh``
    (default: :func:`fleet_mesh` over all local devices): lanes pad to a
    multiple of the device count with *inactive* lane-0 replicas and the
    outputs slice back, bit-for-bit vs the vmap path (pinned by
    ``tests/test_shard_fleet.py``) — the serving fleet spreads resident
    tenants across devices without the batcher noticing.

    On the default path the ``fleet`` argument's buffers are **donated** —
    rebind the returned state (the sharded program, like the S axis's, does
    not donate: padding copies the carry anyway).
    """
    if not shard:
        return _fleet_step_masked_jit(fleet, telemetry, sim_slices,
                                      lane_active)
    mesh = fleet_mesh() if mesh is None else mesh
    d = jax.tree.leaves(fleet)[0].shape[0]
    pad = _fleet_pad(d, mesh)
    new_fleet, outs = _fleet_step_masked_sharded_jit(
        _commit_to_mesh(_pad_fleet_axis(fleet, pad, axis=0), mesh, axis=0),
        _commit_to_mesh(_pad_fleet_axis(telemetry, pad, axis=0), mesh, axis=0),
        _commit_to_mesh(_pad_fleet_axis(sim_slices, pad, axis=0), mesh, axis=0),
        _commit_to_mesh(
            jnp.concatenate([jnp.asarray(lane_active, bool),
                             jnp.zeros((pad,), bool)]) if pad
            else jnp.asarray(lane_active, bool), mesh, axis=0),
        mesh=mesh)
    if pad:
        new_fleet = jax.tree.map(lambda x: x[:d], new_fleet)
        outs = jax.tree.map(lambda x: x[:d], outs)
    return new_fleet, outs


def _run_fleet(fleet: TwinState, telemetry, sim_slices):
    def body(state, inputs):
        telem, sl = inputs
        return jax.vmap(twin_step)(state, telem, sl)

    return jax.lax.scan(body, fleet, (telemetry, sim_slices))


# the fleet carry is donated like twin_step_jit's: run_fleet returns the
# successor state, so the incoming fleet's buffers are reused in place
_run_fleet_jit = jax.jit(_run_fleet, donate_argnums=(0,))


def run_fleet(fleet: TwinState, telemetry, sim_slices,
              *, shard: bool = False, mesh=None
              ) -> tuple[TwinState, WindowOutput]:
    """Twin a whole fleet over a whole horizon in ONE compiled program.

    ``fleet`` is a batched :class:`~repro.core.state.TwinState` (see
    :func:`stack_twin_states`); ``telemetry`` / ``sim_slices`` are
    :class:`~repro.core.state.TelemetrySlice` /
    :class:`~repro.core.state.SimSlice` pytrees whose array leaves lead with
    ``[W, D, ...]`` (windows, datacenters).  Runs ``lax.scan`` over the
    window axis of ``vmap(twin_step)`` over the fleet axis, so D datacenters
    x W windows — prediction, scoring, SLO/bias accumulation and grid-search
    calibration — compile once and execute as a single fused program.

    With ``shard=True`` the D axis is additionally ``shard_map``-ped over
    the devices of ``mesh`` (default: a 1-D :func:`fleet_mesh` over all
    local devices), the same recipe as ``run_scenarios(shard=True)`` on the
    S axis: D pads to a multiple of the device count with lane-0 replicas,
    each device scans its local lanes, and the outputs slice back to the
    true D — **bit-for-bit identical** to the single-device vmap path
    (pinned by ``tests/test_shard_fleet.py``).

    Returns the final fleet state and the per-window outputs stacked
    ``[W, D, ...]``.  Each lane is the exact computation :func:`twin_step`
    performs solo (pinned by ``tests/test_twin_core.py``).

    On the default path the ``fleet`` argument's buffers are **donated**
    (rebind the return value; re-running from the same starting state
    requires a fresh :func:`stack_twin_states`).
    """
    if not shard:
        return _run_fleet_jit(fleet, telemetry, sim_slices)
    mesh = fleet_mesh() if mesh is None else mesh
    d = jax.tree.leaves(fleet)[0].shape[0]
    pad = _fleet_pad(d, mesh)
    new_fleet, outs = _run_fleet_sharded_jit(
        _commit_to_mesh(_pad_fleet_axis(fleet, pad, axis=0), mesh, axis=0),
        _commit_to_mesh(_pad_fleet_axis(telemetry, pad, axis=1), mesh, axis=1),
        _commit_to_mesh(_pad_fleet_axis(sim_slices, pad, axis=1), mesh, axis=1),
        mesh=mesh)
    if pad:
        new_fleet = jax.tree.map(lambda x: x[:d], new_fleet)
        outs = jax.tree.map(lambda x: x[:, :d], outs)
    return new_fleet, outs


# -- fleet-axis sharding: shard_map over D, bit-for-bit vs the vmap path ------

#: mesh axis name the fleet (lane) axis is sharded over
FLEET_AXIS = "fleet"


def fleet_mesh(num_devices: int | None = None):
    """A 1-D device mesh over ``FLEET_AXIS`` (default: all local devices).

    On CPU-only deployments, export
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* process
    start to split the host into N devices (the ``tier1-multidevice`` CI job
    runs the fleet equivalence suite exactly that way).
    """
    devs = jax.devices()  # tracecheck: disable=TC007 — mesh discovery is this helper's purpose
    n = len(devs) if num_devices is None else int(num_devices)
    return jax.make_mesh((n,), (FLEET_AXIS,), (jax.sharding.AxisType.Auto,),
                         devices=devs[:n])


def _fleet_pad(d: int, mesh) -> int:
    """Lanes to add so every device holds an equal shard of D."""
    n_dev = mesh.shape[FLEET_AXIS]
    per_dev = -(-d // n_dev)
    if n_dev > 1:
        # keep >= 2 lanes per device: on four TPU v5e chips (jax 0.9.0) a
        # batch-1 vmap compiles to a different program than the batch-D
        # one, and its mape / calib_mape differ from the vmap path's by
        # 1 ulp — one inactive replica lane keeps the bitwise gate.
        per_dev = max(per_dev, 2)
    return per_dev * n_dev - d


def _pad_fleet_axis(tree, pad: int, axis: int):
    """Pad the fleet axis by replicating lane 0 (sliced off by the caller)."""
    if pad == 0:
        return tree

    def pad_leaf(x):
        sl = [slice(None)] * x.ndim
        sl[axis] = slice(0, 1)
        return jnp.concatenate(
            [x, jnp.repeat(x[tuple(sl)], pad, axis=axis)], axis=axis)

    return jax.tree.map(pad_leaf, tree)


def _commit_to_mesh(tree, mesh, axis: int):
    """Commit every leaf to the mesh, fleet axis sharded over ``FLEET_AXIS``.

    The sharded jits cache on input *sharding*: without this, the first call
    (uncommitted host arrays) and every steady-state call (the previous
    call's ``NamedSharding`` outputs fed back as the carry — the serve
    dispatch loop) would trace two separate programs.  ``device_put`` is a
    no-op for already-matching leaves, so the steady state pays nothing.
    """
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    sharding = NamedSharding(mesh, P(*((None,) * axis), FLEET_AXIS))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)


@functools.partial(jax.jit, static_argnames=("mesh",))
def _run_fleet_sharded_jit(fleet, telemetry, sim_slices, *, mesh):
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(
        _run_fleet, mesh=mesh,
        # fleet-state leaves lead with D; telemetry/sim leaves are [W, D, ..]
        in_specs=(P(FLEET_AXIS), P(None, FLEET_AXIS), P(None, FLEET_AXIS)),
        out_specs=(P(FLEET_AXIS), P(None, FLEET_AXIS)),
        check_vma=False,
    )(fleet, telemetry, sim_slices)


@functools.partial(jax.jit, static_argnames=("mesh",))
def _fleet_step_masked_sharded_jit(fleet, telemetry, sim_slices, lane_active,
                                   *, mesh):
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(
        _fleet_step_masked, mesh=mesh,
        # one window: every input/output leaf leads with the D axis
        in_specs=(P(FLEET_AXIS),) * 4,
        out_specs=(P(FLEET_AXIS), P(FLEET_AXIS)),
        check_vma=False,
    )(fleet, telemetry, sim_slices, lane_active)


# surfaced for the single-compilation regression tests; `_cache_size` is
# private jax API, so its absence must degrade to None, not an import
# error.  The sharded program is a distinct executable with its own cache,
# so each counter sums both paths — a vmap-only workload and a sharded one
# each still count 1.
_run_fleet_caches = tuple(
    getattr(f, "_cache_size", None)
    for f in (_run_fleet_jit, _run_fleet_sharded_jit))
run_fleet._cache_size = (
    (lambda: sum(c() for c in _run_fleet_caches))
    if all(_run_fleet_caches) else None)

_fleet_step_caches = tuple(
    getattr(f, "_cache_size", None)
    for f in (_fleet_step_masked_jit, _fleet_step_masked_sharded_jit))
fleet_step_masked._cache_size = (
    (lambda: sum(c() for c in _fleet_step_caches))
    if all(_fleet_step_caches) else None)


def run_surf_experiment(
    workload,
    dc,
    t_bins: int,
    *,
    calibrate: bool,
    cfg: OrchestratorConfig | None = None,
    base_params: PowerParams = PowerParams(),
    gt=None,
    hitl_policy: Callable[[Proposal], bool | None] | None = None,
) -> TwinRunResult:
    """One E1/E2-style run: trace replay + hidden-model telemetry."""
    cfg = cfg or OrchestratorConfig()
    cfg = dataclasses.replace(cfg, calibrate=calibrate)
    truth = TraceGroundTruth(workload, dc, t_bins, gt)
    twin = DigitalTwin(workload, dc, t_bins, cfg, base_params,
                       hitl_policy=hitl_policy)
    return twin.run(truth.window)
