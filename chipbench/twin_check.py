"""``correct`` of a twinned telemetry stream, window by window.

A stream is a list of windows ``(u_sim, tel_u, tel_p)``; the program's
outputs are, per window, its prediction leaves, its MAPE, the parameters it
predicted with and those it calibrated for the next window.  The reference
(:func:`chipbench.reference.twin_window`, float64) runs the same stream and
is held against the program in four numbers:

* ``pred_rel_gap`` -- largest relative gap of a prediction leaf, the
  reference predicting with the parameters the program calibrated for that
  window (the base parameters for the first);
* ``mape_gap_pp`` -- largest gap of the window MAPE, in percentage points;
* ``calib_regret_pp`` -- how far the history MAPE of the parameters the
  program chose lies from the best candidate's, by the reference's scores
  (so a near tie that rounding breaks the other way costs only the tie's
  width); a choice that is not a candidate of the grid reads infinity;
* ``calib_gap_pp`` -- gap of the program's reported history MAPE of its
  choice, where the program reports one.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench import reference as ref

PRED_LEAVES = ("power_w", "energy_kwh", "utilization", "tflops", "efficiency")


def candidate_grid(spec: dict, base: tuple) -> np.ndarray:
    """``[C, 3]`` rows ``(p_idle, p_max, r)`` of the ``r_only`` grid.

    ``r`` is spaced in float32, as the configuration's grid is stated.
    """
    r = np.linspace(spec["r_lo"], spec["r_hi"], spec["r_points"],
                    dtype=np.float32).astype(np.float64)
    return np.stack([np.full_like(r, base[0]), np.full_like(r, base[1]), r], 1)


def peak_tflops(cfg: dict) -> float:
    """The deployment's peak TFLOP/s, as its read-out counts it."""
    return (cfg["num_hosts"] * cfg["cores_per_host"] * cfg["ghz"] * 1e9
            * cfg["flops_per_cycle"] / 1e12)


def stream_args(cfg: dict) -> dict:
    """The reference's arguments for a stream of the deployment ``cfg``."""
    pm = cfg["power_model"]
    base = (pm["p_idle"], pm["p_max"], pm["r"])
    return dict(base=base, cand=candidate_grid(cfg["calibration"], base),
                peak_tflops=peak_tflops(cfg),
                history_windows=cfg["history_windows"])


def rel_gap(got, want) -> float:
    """Largest ``|got - want|`` over the largest ``|want|`` of a leaf."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return math.inf
    return float(np.max(np.abs(got - want))) / max(
        float(np.max(np.abs(want))), 1e-30)


def _grid_index(cand: np.ndarray, params) -> int:
    """Row of ``cand`` equal (in float32) to ``params``, -1 when none is."""
    p = np.asarray([float(np.asarray(x).reshape(())) for x in params],
                   np.float32)
    hit = np.nonzero((cand.astype(np.float32) == p[None, :]).all(axis=1))[0]
    return int(hit[0]) if hit.size else -1


def compare_stream(windows, outs, *, base: tuple, cand: np.ndarray,
                   peak_tflops: float, history_windows: int) -> dict:
    """The four numbers of one stream (see the module docstring)."""
    hist: list = []
    worst = dict(pred_rel_gap=0.0, mape_gap_pp=0.0, calib_regret_pp=0.0,
                 calib_gap_pp=0.0)
    params = base
    for k, ((u_sim, tel_u, tel_p), out) in enumerate(zip(windows, outs)):
        r = ref.twin_window(np, np.float64, u_sim, params, tel_u, tel_p,
                            hist, cand, peak_tflops=peak_tflops,
                            history_windows=history_windows)
        for leaf in PRED_LEAVES:
            worst["pred_rel_gap"] = max(worst["pred_rel_gap"],
                                        rel_gap(out["pred"][leaf],
                                                r["pred"][leaf]))
        worst["mape_gap_pp"] = max(worst["mape_gap_pp"], _abs_gap(
            out["mape"], float(r["mape"])))
        i = _grid_index(cand, out["params_next"])
        if i < 0 or not np.isfinite(r["cand_mapes"][i]):
            regret = math.inf
        else:
            regret = float(r["cand_mapes"][i] - r["cand_mapes"][r["best"]])
        worst["calib_regret_pp"] = max(worst["calib_regret_pp"], abs(regret))
        if out.get("calib_mape") is not None:
            worst["calib_gap_pp"] = max(worst["calib_gap_pp"], _abs_gap(
                out["calib_mape"],
                float(r["cand_mapes"][i]) if i >= 0 else math.nan))
        params = tuple(float(np.asarray(x).reshape(()))
                       for x in out["params_next"])
    return worst


def _abs_gap(got, want: float) -> float:
    got = float(np.asarray(got, np.float64).reshape(()))
    d = abs(got - want)
    return d if math.isfinite(d) else math.inf


class ReferenceStep:
    """The control of a twinned stream: the reference, computed in
    ``dtype``, in the place of the program's twin step.

    Called as the program's step ``real`` is -- ``(state, telemetry, sim)``
    for one twin, ``(fleet, telemetry, sim, active, **kw)`` for a fleet --
    and returns what it returns.  The program still runs, for the shape of
    its state and output; then every active lane's prediction, MAPE,
    history MAPE and calibrated parameters are replaced by those of
    :func:`reference.twin_window` in ``dtype``, fed with the lane's own
    window and the parameters the lane's state holds.  Each lane keeps its
    own history, started afresh at its window 0.
    """

    def __init__(self, real, cfg: dict, xp, dtype):
        self.real, self.xp, self.dtype = real, xp, dtype
        self.args = stream_args(cfg)
        self.hist: dict = {}
        self._cache_size = real._cache_size

    def __call__(self, state, telemetry, sim, *active, **kw):
        import dataclasses

        import jax.numpy as jnp

        from repro.core.power import PowerParams

        # read the lanes before the program's call may donate the state
        win = np.atleast_1d(np.asarray(state.window))
        par = np.stack([np.atleast_1d(np.asarray(x, np.float64)) for x in
                        (state.params.p_idle, state.params.p_max,
                         state.params.r)], -1)
        u, tu, tp = (np.asarray(x) for x in
                     (sim.u_th, telemetry.u_th, telemetry.power_w))
        if not active:
            u, tu, tp = u[None], tu[None], tp[None]
        on = (np.asarray(active[0]) if active
              else np.ones(len(win), bool))
        new, out = self.real(state, telemetry, sim, *active, **kw)
        pred = {k: np.array(getattr(out.prediction, k), np.float32,
                            ndmin=2) for k in PRED_LEAVES}
        m = np.array(out.mape, np.float32, ndmin=1)
        cm = np.array(out.calib_mape, np.float32, ndmin=1)
        nxt = par.copy()
        for i in np.nonzero(on)[0]:
            if win[i] == 0:
                self.hist[i] = []
            r = ref.twin_window(self.xp, self.dtype, u[i], tuple(par[i]),
                                tu[i], tp[i], self.hist.setdefault(i, []),
                                self.args["cand"],
                                peak_tflops=self.args["peak_tflops"],
                                history_windows=self.args["history_windows"])
            for k in PRED_LEAVES:
                pred[k][i] = np.asarray(r["pred"][k], np.float32)
            m[i] = float(np.asarray(r["mape"], np.float64))
            if r["best"] >= 0:
                nxt[i] = self.args["cand"][r["best"]]
                cm[i] = r["cand_mapes"][r["best"]]

        def back(x):
            return jnp.asarray(x if active else x[0], jnp.float32)

        def params():   # buffers of their own: the state's may be donated
            return PowerParams(*(back(nxt[:, j]) for j in range(3)))

        out = dataclasses.replace(
            out, prediction=dataclasses.replace(
                out.prediction, **{k: back(v) for k, v in pred.items()}),
            mape=back(m), calib_mape=back(cm), params_next=params())
        return dataclasses.replace(new, params=params()), out
