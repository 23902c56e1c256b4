"""Pure functional twin core: pytree ``TwinState`` + ``twin_step``.

The paper's continuous integration cycle (§2.3) — predict the window with
the pipelined parameters, score it against telemetry, calibrate for the next
window, track SLO compliance and estimation bias — is a *state-transition
function*, not an object with side effects.  This module is that function:

    state', output = twin_step(state, telemetry, sim_slice)

``TwinState`` is a registered pytree holding everything the cycle carries
between windows (calibrated :class:`~repro.core.power.PowerParams`, the
fixed-shape calibration history buffers, SLO/bias accumulators, the window
index); ``twin_step`` is pure and shape-stable, so the whole cycle composes
with the JAX transformations the imperative ``Orchestrator`` loop blocked:

  * ``jax.jit(twin_step)`` — one compiled program per window (the
    :class:`~repro.core.orchestrator.Orchestrator` *shell* drives exactly
    this, keeping only I/O, wall-clock pacing, record-keeping and the HITL
    gate host-side);
  * ``jax.vmap(twin_step)`` — a *fleet of twins*: D independent datacenters
    twinned per window by one program (``repro.core.twin.run_fleet``);
  * ``jax.lax.scan`` over windows — a whole horizon in one compilation.

Everything here is deliberately replayable: checkpoint a ``TwinState``
(:func:`save_state` / :func:`load_state`, codec-tagged like every persisted
blob in this repo) and a resumed run reproduces the uninterrupted run's
outputs exactly.

Doctest-sized example (2 hosts, 4-bin windows)::

    >>> import numpy as np
    >>> from repro.traces.schema import DatacenterConfig
    >>> cfg = TwinConfig(bins_per_window=4,
    ...                  dc=DatacenterConfig(num_hosts=2, cores_per_host=4))
    >>> state = init_twin_state(cfg)
    >>> state.hist_u.shape            # [history_windows, bins, hosts]
    (4, 4, 2)
    >>> u = np.full((4, 2), 0.5, np.float32)
    >>> telem = make_telemetry(u, np.full((4,), 420.0, np.float32))
    >>> state2, out = twin_step(state, telem, SimSlice(u_th=u))
    >>> int(state2.window), int(state2.hist_n)
    (1, 1)
    >>> bool(out.mape >= 0)           # window scored against telemetry
    True
    >>> int(state.window)             # purity: the input state is untouched
    0
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec
from repro.core.calibrate import CalibrationSpec, calibrate_traced, candidate_grid
from repro.core.desim import Prediction, predict_metrics
from repro.core.power import PowerParams, mape
from repro.core.slo import NFR1, SLO, observe_bias, observe_slos
from repro.traces.schema import DatacenterConfig
from repro.traces.thermal import PUEParams

Array = jax.Array

#: persisted-state format version (bumped on layout changes)
_STATE_VERSION = 1


@dataclasses.dataclass(frozen=True)
class TwinConfig:
    """Static configuration of the pure core (pytree *aux data*).

    Hashable — it rides the ``TwinState`` treedef, so it is part of the jit
    cache key and never traced.  Mirrors the twin-loop fields of
    :class:`~repro.core.orchestrator.OrchestratorConfig`; the shell-only
    knobs (acceleration pacing, proposal caps) stay in the shell.
    """

    bins_per_window: int = 36
    dc: DatacenterConfig = DatacenterConfig()
    calibration: CalibrationSpec = CalibrationSpec()
    calibrate: bool = True
    history_windows: int = 4
    power_model: str = "opendc"
    kernel_backend: str = "xla"
    slos: tuple[SLO, ...] = (NFR1,)
    #: dynamic-PUE model: when set, window predictions report *facility*
    #: power (IT draw x PUE(load, ambient)) — frozen/hashable, so it rides
    #: the jit cache key like every other static knob.
    pue: PUEParams | None = None
    #: full-horizon DES resident in the state: when positive, ``TwinState``
    #: carries a ``[sim_bins, H]`` utilization field (``sim_u``) and
    #: ``twin_step`` slices its own window from it whenever the caller
    #: passes ``SimSlice(u_th=None)`` — the topology-applying feedback loop
    #: (an accepted proposal re-simulates and swaps this field) needs the
    #: twin to own its simulation.  0 (the default) keeps the incumbent
    #: layout: no extra leaf, the shell feeds per-window slices.
    sim_bins: int = 0


@dataclasses.dataclass(frozen=True)
class TwinState:
    """Everything the windowed cycle carries between windows (pytree).

    Array children (all fixed-shape, so ``twin_step`` never retraces):

    ================  =====================  ===============================
    field             shape / dtype          meaning
    ================  =====================  ===============================
    ``params``        scalars, float32       pipelined power params: the
                                             calibration result C_{k-1} the
                                             next prediction S_k must use
    ``base_params``   scalars, float32       reset target when a calibration
                                             window is undefined (all-zero)
    ``cand``          leaves ``[C]``         the precomputed candidate grid
                                             (host-built, bitwise identical
                                             to ``candidate_grid``)
    ``hist_u``        ``[K, Tw, H]`` f32     calibration history: utilization
    ``hist_p``        ``[K, Tw]`` f32        calibration history: power
    ``hist_n``        int32                  filled history slots (<= K)
    ``window``        int32                  next window index
    ``slo_samples``   ``[n_slo]`` int32      SLO accumulator: observations
    ``slo_compliant`` ``[n_slo]`` int32      SLO accumulator: compliant
    ``bias_under``    int32                  bias split (paper Fig. 6)
    ``bias_over``     int32
    ``bias_ties``     int32
    ``sim_u``         ``[sim_bins, H]`` f32  full-horizon DES utilization
                                             (``None`` unless
                                             ``cfg.sim_bins > 0``)
    ================  =====================  ===============================

    With ``CalibrationSpec(per_host=True)`` the ``params`` / ``base_params``
    leaves are ``[H]`` rows instead of scalars — one calibrated power model
    per host, threaded straight into prediction (the power models broadcast
    trailing host-dim parameters).

    History buffers are chronological with zero-padding at the tail; padded
    bins have zero measured power, which the MAPE kernel already excludes,
    so a partially-filled buffer scores like the old variable-length
    concatenation.  ``cfg`` is aux data (static, hashable).  ``sim_u=None``
    is an empty pytree subtree, so the default layout's leaf list (and every
    existing golden/checkpoint) is unchanged.
    """

    params: PowerParams
    base_params: PowerParams
    cand: PowerParams
    hist_u: Array
    hist_p: Array
    hist_n: Array
    window: Array
    slo_samples: Array
    slo_compliant: Array
    bias_under: Array
    bias_over: Array
    bias_ties: Array
    sim_u: Array | None = None
    cfg: TwinConfig = TwinConfig()


jax.tree_util.register_pytree_node(
    TwinState,
    lambda s: ((s.params, s.base_params, s.cand, s.hist_u, s.hist_p,
                s.hist_n, s.window, s.slo_samples, s.slo_compliant,
                s.bias_under, s.bias_over, s.bias_ties, s.sim_u), s.cfg),
    lambda cfg, c: TwinState(*c, cfg=cfg),
)


@dataclasses.dataclass(frozen=True)
class TelemetrySlice:
    """One window of physical-twin telemetry as a device-ready pytree.

    ``valid`` masks the whole observation: with ``valid=False`` the step
    still predicts (the twin keeps running) but scores nothing, learns
    nothing and leaves every accumulator untouched — the pure-core encoding
    of "this window's telemetry has not landed".
    """

    u_th: Array      # [Tw, H] float32 measured utilization
    power_w: Array   # [Tw] float32 measured total power
    valid: Array     # bool scalar


jax.tree_util.register_pytree_node(
    TelemetrySlice,
    lambda t: ((t.u_th, t.power_w, t.valid), None),
    lambda _, c: TelemetrySlice(*c),
)


def make_telemetry(u_th, power_w, valid: bool = True) -> TelemetrySlice:
    """Build a :class:`TelemetrySlice` from host arrays (float32-cast)."""
    return TelemetrySlice(
        u_th=jnp.asarray(u_th, jnp.float32),
        power_w=jnp.asarray(power_w, jnp.float32),
        valid=jnp.asarray(valid, bool),
    )


def empty_telemetry(bins_per_window: int, num_hosts: int) -> TelemetrySlice:
    """The ``valid=False`` placeholder for a window with no telemetry."""
    return TelemetrySlice(
        u_th=jnp.zeros((bins_per_window, num_hosts), jnp.float32),
        power_w=jnp.zeros((bins_per_window,), jnp.float32),
        valid=jnp.asarray(False),
    )


@dataclasses.dataclass(frozen=True)
class SimSlice:
    """The simulation engine's window slice the core predicts from.

    ``u_th`` is the window's ``[Tw, H]`` slice of the full-horizon DES
    utilization field (the DES itself is power-parameter independent and
    stays outside the per-window step — see ``Orchestrator._ensure_sim``).
    With ``TwinConfig.sim_bins > 0`` the state owns the full horizon and
    ``u_th`` may be ``None``: ``twin_step`` then slices the window from
    ``state.sim_u`` itself.  ``carbon_intensity`` / ``ambient_c`` /
    ``price`` are the optional ``[Tw]`` forecast slices (gCO2/kWh, deg C,
    $/kWh) the read-out folds into gCO2, dynamic PUE and energy cost.
    """

    u_th: Array | None = None
    carbon_intensity: Array | None = None
    ambient_c: Array | None = None
    price: Array | None = None


jax.tree_util.register_pytree_node(
    SimSlice,
    lambda s: ((s.u_th, s.carbon_intensity, s.ambient_c, s.price), None),
    lambda _, c: SimSlice(*c),
)


@dataclasses.dataclass(frozen=True)
class WindowOutput:
    """Per-window read-out of one ``twin_step`` (pytree).

    ``mape`` and ``calib_mape`` are NaN when the window had no (valid)
    telemetry; ``params_used`` are the pipelined parameters the prediction
    ran with, ``params_next`` the calibration result shipped to the next
    window (equal to ``params_used`` when nothing was learned).
    """

    prediction: Prediction
    mape: Array            # f32 scalar, % (NaN without telemetry)
    calib_mape: Array      # f32 scalar, best candidate's history MAPE
    params_used: PowerParams
    params_next: PowerParams
    window: Array          # int32 scalar


jax.tree_util.register_pytree_node(
    WindowOutput,
    lambda o: ((o.prediction, o.mape, o.calib_mape, o.params_used,
                o.params_next, o.window), None),
    lambda _, c: WindowOutput(*c),
)


def _scalar_param(x, name: str, hosts: int | None = None) -> Array:
    """Base-parameter leaf: scalar, or a ``[hosts]`` row in per-host mode."""
    a = jnp.asarray(x, jnp.float32)
    if hosts is not None:
        if a.ndim == 0 or a.size == 1:
            return jnp.full((hosts,), a.reshape(()), jnp.float32)
        if a.shape != (hosts,):
            raise ValueError(
                f"per-host base params must be scalar or [{hosts}]; "
                f"{name} has shape {a.shape}")
        return a
    if a.ndim != 0 and a.size != 1:
        raise ValueError(
            f"pure-core base params must be scalar; {name} has shape "
            f"{a.shape}.  Per-host parameters need "
            "CalibrationSpec(per_host=True), which carries [H] rows; the "
            "fleet-level calibrator output is scalar by construction.")
    return a.reshape(())


def init_twin_state(cfg: TwinConfig,
                    base_params: PowerParams = PowerParams(),
                    sim_u=None) -> TwinState:
    """Fresh ``TwinState``: base parameters, empty history, zero counters.

    The candidate grid is precomputed host-side here (one
    :func:`~repro.core.calibrate.candidate_grid` call) and carried as state
    leaves, so every subsequent ``twin_step`` is pure array math.

    With ``cfg.sim_bins > 0`` the state carries the full-horizon DES
    utilization field: pass ``sim_u`` (``[sim_bins, H]``) to seed it, or
    leave it ``None`` for a zero field the shell fills in later.  With
    ``cfg.calibration.per_host`` the parameter leaves are ``[H]`` rows
    (scalar bases broadcast; length-``H`` vectors pass through).
    """
    k, tw, h = cfg.history_windows, cfg.bins_per_window, cfg.dc.num_hosts
    hosts = h if cfg.calibration.per_host else None
    base = PowerParams(
        p_idle=_scalar_param(base_params.p_idle, "p_idle", hosts),
        p_max=_scalar_param(base_params.p_max, "p_max", hosts),
        r=_scalar_param(base_params.r, "r", hosts))
    if cfg.sim_bins > 0:
        if sim_u is None:
            sim_u = jnp.zeros((cfg.sim_bins, h), jnp.float32)
        else:
            sim_u = jnp.asarray(sim_u, jnp.float32)
            if sim_u.shape != (cfg.sim_bins, h):
                raise ValueError(
                    f"sim_u must be [{cfg.sim_bins}, {h}] "
                    f"(cfg.sim_bins x num_hosts); got {sim_u.shape}")
    elif sim_u is not None:
        raise ValueError("sim_u given but cfg.sim_bins == 0")
    state = TwinState(
        sim_u=sim_u,
        params=base,
        base_params=base,
        cand=candidate_grid(cfg.calibration, base),
        hist_u=jnp.zeros((k, tw, h), jnp.float32),
        hist_p=jnp.zeros((k, tw), jnp.float32),
        hist_n=jnp.asarray(0, jnp.int32),
        window=jnp.asarray(0, jnp.int32),
        slo_samples=jnp.zeros((len(cfg.slos),), jnp.int32),
        slo_compliant=jnp.zeros((len(cfg.slos),), jnp.int32),
        bias_under=jnp.asarray(0, jnp.int32),
        bias_over=jnp.asarray(0, jnp.int32),
        bias_ties=jnp.asarray(0, jnp.int32),
        cfg=cfg,
    )
    # de-alias the leaves: params/base_params start as the *same* arrays
    # (and scalar constants may share cached buffers), but twin_step_jit
    # donates the state — XLA rejects the same buffer donated twice
    return jax.tree.map(lambda x: jnp.array(x), state)


def _push(buf: Array, new: Array, n: Array) -> Array:
    """Append ``new`` to a chronological ``[K, ...]`` buffer.

    Writes at slot ``n`` while the buffer is filling (padding stays at the
    tail) and shifts left once full — the buffer always reads oldest →
    newest, like the imperative calibrator's ``history[-K:]`` concat.
    """
    k = buf.shape[0]
    shifted = jnp.concatenate([buf[1:], new[None]], axis=0)
    written = jax.lax.dynamic_update_slice_in_dim(
        buf, new[None], jnp.minimum(n, k - 1), axis=0)
    return jnp.where(n >= k, shifted, written)


def _where_tree(cond, a, b):
    return jax.tree.map(lambda x, y: jnp.where(cond, x, y), a, b)


def twin_step(state: TwinState, telemetry: TelemetrySlice,
              sim_slice: SimSlice) -> tuple[TwinState, WindowOutput]:
    """One pure window of the continuous twinning cycle (paper Fig. 3).

    S_k: predict the window from ``sim_slice`` with the *pipelined*
    parameters (``state.params`` — the C_{k-1} result).  Then, when the
    telemetry is valid: score the prediction (MAPE), update the SLO and
    bias accumulators, push the observation into the history buffers and
    run C_k (grid-search calibration over the history) so S_{k+1} predicts
    with fresh parameters.  Pure and fixed-shape: compose freely with
    ``jit``, ``vmap`` (fleets of twins) and ``scan`` (whole horizons).
    """
    cfg = state.cfg
    params = state.params

    # S_k — prediction with the pipelined parameters.  When the state owns
    # the full-horizon DES (cfg.sim_bins > 0) and the caller passes no
    # window slice, slice it here: the twin simulates from *its own* field,
    # which topology-applying feedback may have re-simulated.
    u_win = sim_slice.u_th
    if u_win is None:
        if state.sim_u is None:
            raise ValueError(
                "SimSlice.u_th is None but the state carries no sim_u "
                "(TwinConfig.sim_bins == 0)")
        u_win = jax.lax.dynamic_slice_in_dim(
            state.sim_u, state.window * cfg.bins_per_window,
            cfg.bins_per_window, axis=0)
    with jax.named_scope("opendt.twin_predict"):
        pred = predict_metrics(u_win, params, cfg.dc,
                               model=cfg.power_model,
                               carbon_intensity=sim_slice.carbon_intensity,
                               ambient_c=sim_slice.ambient_c,
                               price=sim_slice.price,
                               pue=cfg.pue,
                               backend=cfg.kernel_backend)

    # Scoring: window MAPE against measured power (NaN without telemetry).
    valid = telemetry.valid
    m = jnp.where(valid, mape(telemetry.power_w, pred.power_w), jnp.nan)

    slo_samples, slo_compliant = observe_slos(
        cfg.slos, state.slo_samples, state.slo_compliant, m, valid,
        metric="mape")
    under, over, ties = observe_bias(
        state.bias_under, state.bias_over, state.bias_ties,
        telemetry.power_w, pred.power_w, valid)

    hist_u, hist_p, hist_n = state.hist_u, state.hist_p, state.hist_n
    params_next = params
    calib_mape = jnp.asarray(jnp.nan, jnp.float32)
    if cfg.calibrate:
        # C_k — masked history push + grid search for S_{k+1}.
        with jax.named_scope("opendt.twin_calibrate"):
            hist_u = jnp.where(valid, _push(state.hist_u, telemetry.u_th,
                                            state.hist_n), state.hist_u)
            hist_p = jnp.where(valid, _push(state.hist_p, telemetry.power_w,
                                            state.hist_n), state.hist_p)
            hist_n = jnp.where(valid,
                               jnp.minimum(state.hist_n + 1,
                                           cfg.history_windows),
                               state.hist_n)
            k, tw, h = hist_u.shape
            new_params, best_mape = calibrate_traced(
                hist_u.reshape(k * tw, h), hist_p.reshape(k * tw),
                state.cand, cfg.calibration, state.base_params,
                backend=cfg.kernel_backend)
            params_next = _where_tree(valid, new_params, params)
            calib_mape = jnp.where(valid, best_mape, jnp.nan)

    new_state = TwinState(
        params=params_next,
        base_params=state.base_params,
        cand=state.cand,
        hist_u=hist_u,
        hist_p=hist_p,
        hist_n=hist_n,
        window=state.window + 1,
        slo_samples=slo_samples,
        slo_compliant=slo_compliant,
        bias_under=under,
        bias_over=over,
        bias_ties=ties,
        sim_u=state.sim_u,
        cfg=cfg,
    )
    out = WindowOutput(prediction=pred, mape=m, calib_mape=calib_mape,
                       params_used=params, params_next=params_next,
                       window=state.window)
    return new_state, out


#: the shared jitted step the imperative shell (and simple callers) drive —
#: one compilation per (shapes, cfg) combination, shared across instances.
#: The window carry is donated: every caller rebinds ``state, out =
#: twin_step_jit(state, ...)``, so the incoming TwinState's buffers (the
#: [K, Tw, H] history above all) are dead after the call and XLA reuses
#: them for the outgoing state instead of double-buffering.  Reading a
#: donated input afterwards raises — keep a reference to the *new* state
#: (or use ``jax.jit(twin_step)`` for a non-donating step).
twin_step_jit = jax.jit(twin_step, donate_argnums=(0,))


# -- checkpoint / resume ------------------------------------------------------

def state_to_bytes(state: TwinState) -> bytes:
    """Encode a ``TwinState`` as a codec-tagged compressed msgpack blob.

    Same optional-dependency story as every persisted blob in this repo
    (:mod:`repro.core.codec`): zstd when available, stdlib zlib otherwise,
    one codec-id byte so either reader opens either blob.  The byte form is
    what checkpoints (:func:`save_state`), the streaming service's session
    store (:mod:`repro.serve.sessions`) and its result cache
    (:mod:`repro.serve.cache`) all share.
    """
    leaves, treedef = jax.tree_util.tree_flatten(state)
    del treedef  # reconstructed from cfg on load
    cfg = state.cfg
    payload = {
        "version": _STATE_VERSION,
        "cfg": {
            "bins_per_window": cfg.bins_per_window,
            "dc": dataclasses.asdict(cfg.dc),
            "calibration": dataclasses.asdict(cfg.calibration),
            "calibrate": cfg.calibrate,
            "history_windows": cfg.history_windows,
            "power_model": cfg.power_model,
            "kernel_backend": cfg.kernel_backend,
            "slos": [dataclasses.asdict(s) for s in cfg.slos],
            # None when dynamic PUE is off; old readers ignore the key,
            # old files load with pue=None (tolerant .get on load).
            "pue": (dataclasses.asdict(cfg.pue)
                    if cfg.pue is not None else None),
            # 0 when the shell owns the DES; old files load with 0
            # (tolerant .get on load), so the leaf lists line up.
            "sim_bins": cfg.sim_bins,
        },
        "leaves": [codec.pack_array(x) for x in leaves],
    }
    return codec.dumps(payload)


def state_from_bytes(blob: bytes) -> TwinState:
    """Decode a ``TwinState`` from :func:`state_to_bytes` (bit-identical)."""
    payload = codec.loads(blob)
    if payload["version"] != _STATE_VERSION:
        raise ValueError(
            f"unsupported TwinState version {payload['version']} "
            f"(this build reads {_STATE_VERSION})")
    c = payload["cfg"]
    cfg = TwinConfig(
        bins_per_window=c["bins_per_window"],
        dc=DatacenterConfig(**c["dc"]),
        calibration=CalibrationSpec(**c["calibration"]),
        calibrate=c["calibrate"],
        history_windows=c["history_windows"],
        power_model=c["power_model"],
        kernel_backend=c["kernel_backend"],
        slos=tuple(SLO(**s) for s in c["slos"]),
        pue=(PUEParams(**c["pue"]) if c.get("pue") is not None else None),
        sim_bins=c.get("sim_bins", 0),
    )
    template = init_twin_state(cfg)
    treedef = jax.tree_util.tree_structure(template)
    leaves = [jnp.asarray(codec.unpack_array(rec))
              for rec in payload["leaves"]]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def save_state(state: TwinState, path: str) -> None:
    """Persist a ``TwinState`` (:func:`state_to_bytes`) to ``path``."""
    with open(path, "wb") as f:
        f.write(state_to_bytes(state))


def load_state(path: str) -> TwinState:
    """Load a ``TwinState`` written by :func:`save_state`.

    The resumed state is bit-identical to the saved one, so a resumed run
    reproduces the uninterrupted run exactly (pinned by
    ``tests/test_twin_core.py``).
    """
    with open(path, "rb") as f:
        return state_from_bytes(f.read())
