"""Fused DES readout (Pallas): the whole per-bin metric pipeline, one pass.

The post-scan readout of the scenario engine (`scenarios._predict_masked`)
re-reads the utilization field ``[T, H]`` once per metric: per-host power
shape, online masking, the idle floor, mean utilization, dynamic PUE,
cap/throttle enforcement, then energy/gCO2/cost integration.  At
``BENCH_whatif.json`` scale that readout is ~half of every DES call.  This
kernel fuses the pipeline into one VMEM pass per ``[Tb, Hp]`` tile: the
utilization block is read once and all nine ``Prediction`` leaves come out
as ``[Tb, 1]`` columns.

Grid:   (T_tiles,)
Blocks: u (Tb, Hp);  per-host rows (1, Hp);  per-bin columns (Tb, 1);
        packed scalar row (1, 128);  9 outputs (Tb, 1).

Every axis of the scenario engine is an *operand*, never a recompile:

  * inactive hosts — ``mask`` row zeros (idle watts and the utilization
    denominator both respect it);
  * failures — ``fail_start``/``fail_end``/``fail_kill`` rows; the per-bin
    online mask is rebuilt in-kernel from ``broadcasted_iota`` time ids,
    so no ``[T, H]`` availability tensor is ever materialized;
  * dynamic PUE — identity parameters (base 1, coeffs 0) are an IEEE-exact
    no-op (``x * 1.0`` and ``+ 0.0``), so the PUE multiply is always
    compiled in and axis-free lanes stay bitwise on the one program;
  * caps — ``+inf`` is the uncapped sentinel (``min(x, inf) == x``);
  * absent carbon/price traces — zero columns (outputs ignored upstream).

``des_readout_ref`` is the XLA fallback: it packs operands with the *same*
padding and runs the *same* tile function via ``lax.map`` over the same
tile decomposition, so the interpret-mode kernel and the reference agree
**bit for bit** in f32 (pinned by ``tests/test_des_kernel.py``).  The
legacy unfused readout and the f64 oracle are tolerance gates, not bitwise
ones: summing a zero-padded 128-lane row is not IEEE-identical to summing
the unpadded row.

Precision policy (``precision="bf16"``): sustainability leaves (power,
energy, gCO2, cost, PUE, demand) and utilization stay f32 — the oracle
tolerance in ``tests/test_oracle.py`` is rtol 1e-4..2e-4, 20-40x tighter
than one bf16 ulp (2^-8) — while the derived performance leaves (tflops,
efficiency) are computed in bf16 and stored as f32.  The policy is pinned
against ``tests/golden/readout_bf16.npz``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

Array = jax.Array

TB_T = 512

#: output order of the fused readout — Prediction's array leaves
READOUT_FIELDS = ("power_w", "energy_kwh", "tflops", "utilization",
                  "efficiency", "gco2", "power_demand_w", "pue",
                  "energy_cost")

#: floor under the log in the exp/log power form (0**r -> ~0, never -inf)
_LOG_FLOOR = 1e-30


def _shape_term(u: Array, r: Array, model: str) -> Array:
    """Power-curve shape term of :data:`repro.core.power.POWER_MODELS`.

    ``u`` must be pre-clipped to [0, 1].  The opendc exponent uses the
    ``exp(r * log(u))`` form (Pallas/TPU has no f32 ``pow`` primitive)
    — within 1 ulp of ``u**r`` and exactly reproduced by the XLA
    reference.
    """
    if model == "opendc":
        return 2.0 * u - jnp.exp(r * jnp.log(jnp.maximum(u, _LOG_FLOOR)))
    if model == "linear":
        return u
    if model == "sqrt":
        return jnp.sqrt(u)
    if model == "cubic":
        return u * u * u
    raise ValueError(f"unknown power model {model!r}")


def _tile_readout(u, pi, pm, rr, mask, fs, fe, kill, cap, ci, amb, prc,
                  scal, t0, *, model: str, precision: str,
                  dt_seconds: float, tb_t: int):
    """The fused readout over one ``[tb_t, Hp]`` tile (pure jnp).

    Shared verbatim by the Pallas kernel body and the XLA reference so the
    two paths execute the identical op sequence on identical tile shapes.
    ``t0`` is the absolute bin index of the tile's first row; ``scal`` is
    the packed ``(1, 128)`` scalar row
    ``[peak_tflops, pue_base, pue_load_coeff, pue_amb_coeff, pue_amb_ref]``.
    """
    t_ids = t0 + jax.lax.broadcasted_iota(jnp.int32, (tb_t, 1), 0)
    # per-bin availability: outage hosts draw nothing inside their window
    off = (kill > 0.0) & (t_ids >= fs) & (t_ids < fe)            # [Tb, Hp]
    on = jnp.where(off, 0.0, 1.0) * mask
    uc = jnp.clip(u, 0.0, 1.0)
    host_p = pi + (pm - pi) * _shape_term(uc, rr, model)
    it_demand = jnp.sum(host_p * on, axis=1, keepdims=True)      # [Tb, 1]
    idle_floor = jnp.sum(pi * on, axis=1, keepdims=True)
    util_raw = jnp.sum(u * on, axis=1, keepdims=True) / jnp.maximum(
        jnp.sum(on, axis=1, keepdims=True), 1.0)
    peak, p_base, p_load, p_amb, p_ref = (
        scal[:, i:i + 1] for i in range(5))                      # [1, 1] each
    # dynamic PUE (traces/thermal.dynamic_pue); identity params -> exact 1.0
    load = jnp.clip(util_raw, 0.0, 1.0)
    pue = p_base + p_load * (1.0 - load)
    pue = pue + p_amb * jnp.maximum(amb - p_ref, 0.0)
    demand = it_demand * pue
    floor = idle_floor * pue
    # cap enforcement + linear throttle (scenarios._predict_masked)
    exceeded = demand > cap
    power = jnp.minimum(demand, cap)
    throttle = jnp.clip(
        (cap - floor) / jnp.maximum(demand - floor, 1e-9), 0.0, 1.0)
    e = power * (dt_seconds / 3600.0) / 1000.0
    util = jnp.where(exceeded, util_raw * throttle, util_raw)
    if precision == "bf16":
        # performance derivatives only; sustainability stays f32 (see
        # module docstring) — stored back as f32 for structural stability
        tf16 = util.astype(jnp.bfloat16) * peak.astype(jnp.bfloat16)
        eff = (tf16 / jnp.maximum(e, 1e-9).astype(jnp.bfloat16)
               ).astype(jnp.float32)
        tflops = tf16.astype(jnp.float32)
    elif precision == "f32":
        tflops = util * peak
        eff = tflops / jnp.maximum(e, 1e-9)
    else:
        raise ValueError(f"unknown precision policy {precision!r}")
    gco2 = e * ci
    cost = e * prc
    return power, e, tflops, util, eff, gco2, demand, pue, cost


def _kernel(u_ref, pi_ref, pm_ref, rr_ref, mk_ref, fs_ref, fe_ref, kl_ref,
            cap_ref, ci_ref, amb_ref, prc_ref, scal_ref, *out_refs,
            model: str, precision: str, dt_seconds: float, tb_t: int):
    outs = _tile_readout(
        u_ref[...], pi_ref[...], pm_ref[...], rr_ref[...], mk_ref[...],
        fs_ref[...], fe_ref[...], kl_ref[...], cap_ref[...], ci_ref[...],
        amb_ref[...], prc_ref[...], scal_ref[...],
        pl.program_id(0) * tb_t,
        model=model, precision=precision, dt_seconds=dt_seconds, tb_t=tb_t)
    for ref, val in zip(out_refs, outs):
        ref[...] = val


def _pack_operands(u_th, *, p_idle, p_max, r, mask, cap_t, intensity,
                   ambient, price, peak_tflops, pue_base, pue_amb_coeff,
                   pue_amb_ref, pue_load_coeff, fail_start, fail_end,
                   fail_kill, tb_t):
    """Pad every axis into kernel operands (shared by pallas and ref).

    Padded host lanes carry ``p_idle = p_max = 0``, ``r = 1`` and a zero
    mask; padded time rows carry a ``+inf`` cap (all finite outputs, then
    sliced off).  Both paths call this, so their operand bits are equal by
    construction.
    """
    t, h = u_th.shape
    hp = pl.cdiv(h, 128) * 128
    tp = pl.cdiv(t, tb_t) * tb_t
    f32 = jnp.float32
    u = jnp.pad(u_th.astype(f32), ((0, tp - t), (0, hp - h)))

    def row(x, fill=0.0, dtype=f32):
        x = jnp.broadcast_to(jnp.asarray(x, dtype), (h,))
        return jnp.pad(x, (0, hp - h), constant_values=fill)[None, :]

    pi = row(p_idle)
    pm = row(p_max)
    rr = row(r, fill=1.0)
    mk = row(jnp.ones((h,), f32) if mask is None
             else jnp.asarray(mask).astype(f32))
    if fail_start is None:
        fs = jnp.full((1, hp), np.iinfo(np.int32).max, jnp.int32)
        fe = jnp.zeros((1, hp), jnp.int32)
        kl = jnp.zeros((1, hp), f32)
    else:
        fs = row(fail_start, fill=np.iinfo(np.int32).max, dtype=jnp.int32)
        fe = row(fail_end, dtype=jnp.int32)
        kl = row(jnp.asarray(fail_kill).astype(f32))

    def col(x, fill=0.0):
        x = jnp.broadcast_to(jnp.asarray(x, f32), (t,))
        return jnp.pad(x, (0, tp - t), constant_values=fill)[:, None]

    cap = col(jnp.inf if cap_t is None else cap_t, fill=np.inf)
    ci = col(0.0 if intensity is None else intensity)
    amb = col(0.0 if ambient is None else ambient)
    prc = col(0.0 if price is None else price)
    scal = jnp.zeros((1, 128), f32)
    for i, v in enumerate((peak_tflops, pue_base, pue_load_coeff,
                           pue_amb_coeff, pue_amb_ref)):
        scal = scal.at[0, i].set(jnp.asarray(v, f32))
    return (u, pi, pm, rr, mk, fs, fe, kl, cap, ci, amb, prc, scal), (t, tp, hp)


def des_readout_pallas(
    u_th: Array,
    *,
    p_idle,
    p_max,
    r,
    mask: Array | None = None,
    cap_t: Array | None = None,
    intensity: Array | None = None,
    ambient: Array | None = None,
    price: Array | None = None,
    peak_tflops=1.0,
    pue_base=1.0,
    pue_amb_coeff=0.0,
    pue_amb_ref=18.0,
    pue_load_coeff=0.0,
    fail_start: Array | None = None,
    fail_end: Array | None = None,
    fail_kill: Array | None = None,
    model: str = "opendc",
    precision: str = "f32",
    dt_seconds: float = 300.0,
    interpret: bool = False,
    tb_t: int = TB_T,
) -> dict[str, Array]:
    """Fused scenario readout, Pallas path.

    Returns ``{field: [T] f32}`` for every name in :data:`READOUT_FIELDS`
    (always all nine — callers map absent axes back to ``None`` leaves).
    vmap-safe: every per-lane quantity is an operand, so the scenario
    engine vmaps this over S without retracing.
    """
    operands, (t, tp, hp) = _pack_operands(
        u_th, p_idle=p_idle, p_max=p_max, r=r, mask=mask, cap_t=cap_t,
        intensity=intensity, ambient=ambient, price=price,
        peak_tflops=peak_tflops, pue_base=pue_base,
        pue_amb_coeff=pue_amb_coeff, pue_amb_ref=pue_amb_ref,
        pue_load_coeff=pue_load_coeff, fail_start=fail_start,
        fail_end=fail_end, fail_kill=fail_kill, tb_t=tb_t)
    kernel = functools.partial(
        _kernel, model=model, precision=precision,
        dt_seconds=dt_seconds, tb_t=tb_t)
    row_spec = pl.BlockSpec((1, hp), lambda ti: (0, 0))
    col_spec = pl.BlockSpec((tb_t, 1), lambda ti: (ti, 0))
    shape_t = jax.ShapeDtypeStruct((tp, 1), jnp.float32)
    outs = pl.pallas_call(
        kernel,
        grid=(tp // tb_t,),
        in_specs=[
            pl.BlockSpec((tb_t, hp), lambda ti: (ti, 0)),       # u
            row_spec, row_spec, row_spec, row_spec,             # pi pm rr mk
            row_spec, row_spec, row_spec,                       # fs fe kl
            col_spec, col_spec, col_spec, col_spec,             # cap ci amb prc
            pl.BlockSpec((1, 128), lambda ti: (0, 0)),          # scal
        ],
        out_specs=[col_spec] * len(READOUT_FIELDS),
        out_shape=[shape_t] * len(READOUT_FIELDS),
        interpret=interpret,
        name="des_readout",
    )(*operands)
    return {k: v[:t, 0] for k, v in zip(READOUT_FIELDS, outs)}


def des_readout_ref(
    u_th: Array,
    *,
    p_idle,
    p_max,
    r,
    mask: Array | None = None,
    cap_t: Array | None = None,
    intensity: Array | None = None,
    ambient: Array | None = None,
    price: Array | None = None,
    peak_tflops=1.0,
    pue_base=1.0,
    pue_amb_coeff=0.0,
    pue_amb_ref=18.0,
    pue_load_coeff=0.0,
    fail_start: Array | None = None,
    fail_end: Array | None = None,
    fail_kill: Array | None = None,
    model: str = "opendc",
    precision: str = "f32",
    dt_seconds: float = 300.0,
    tb_t: int = TB_T,
) -> dict[str, Array]:
    """XLA reference/fallback of :func:`des_readout_pallas`.

    Identical operand packing and the identical tile function, mapped over
    the identical tile decomposition (``lax.map`` = the grid loop) — so in
    f32 the two paths are bitwise equal, not just close.
    """
    operands, (t, tp, hp) = _pack_operands(
        u_th, p_idle=p_idle, p_max=p_max, r=r, mask=mask, cap_t=cap_t,
        intensity=intensity, ambient=ambient, price=price,
        peak_tflops=peak_tflops, pue_base=pue_base,
        pue_amb_coeff=pue_amb_coeff, pue_amb_ref=pue_amb_ref,
        pue_load_coeff=pue_load_coeff, fail_start=fail_start,
        fail_end=fail_end, fail_kill=fail_kill, tb_t=tb_t)
    u, pi, pm, rr, mk, fs, fe, kl, cap, ci, amb, prc, scal = operands
    n_tiles = tp // tb_t

    def tile(ti):
        s = ti * tb_t
        outs = _tile_readout(
            jax.lax.dynamic_slice(u, (s, 0), (tb_t, hp)),
            pi, pm, rr, mk, fs, fe, kl,
            jax.lax.dynamic_slice(cap, (s, 0), (tb_t, 1)),
            jax.lax.dynamic_slice(ci, (s, 0), (tb_t, 1)),
            jax.lax.dynamic_slice(amb, (s, 0), (tb_t, 1)),
            jax.lax.dynamic_slice(prc, (s, 0), (tb_t, 1)),
            scal, s, model=model, precision=precision,
            dt_seconds=dt_seconds, tb_t=tb_t)
        return tuple(o[:, 0] for o in outs)

    outs = jax.lax.map(tile, jnp.arange(n_tiles, dtype=jnp.int32))
    return {k: v.reshape(tp)[:t]
            for k, v in zip(READOUT_FIELDS, outs)}
