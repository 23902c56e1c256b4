"""Fused grid-search MAPE Pallas kernel (the Self-Calibrator's hot spot).

The calibrator evaluates C candidate power-model parameterizations against a
cached utilization window [T, H] (see core/calibrate.py).  The naive
formulation materializes a [C, T] (or worse, [C, T, H]) tensor in HBM; with
the beyond-paper joint grid C reaches 10^4-10^5 and the window grows with the
history length, so the intermediate dominates HBM traffic.

TPU adaptation: tile candidates x time, and stream the host axis.  The
utilization window enters transposed, ``[Hp, Tb]`` (hosts on sublanes, time
bins on lanes), so one host's row is a lane-dense ``[1, Tb]`` vector.  Each
grid step keeps a ``[Cb, Tb]`` accumulator of ``sum_h u^r`` and walks the
hosts eight rows (one sublane group) at a time in a ``fori_loop``: nothing
``[Tb, Hp, Cb]``-shaped ever exists, so VMEM holds only the utilization
block and a few ``[Cb, Tb]`` tiles (well under the 16 MiB scoped limit at
any host count a chip twins).  Per-candidate |rel-err| partial sums
accumulate in the output block across the T grid dimension (TPU grids
execute sequentially, so the last grid axis is a legal reduction axis).

Grid:     (C_tiles, T_tiles)               (T last => sequential reduction)
Blocks:   u:    (Hp, Tb)   VMEM            Hp = H padded to 8 sublanes
          real: (1, Tb)    VMEM            Tb a multiple of 128 lanes
          p_*:  (Cb, 1)    VMEM
          out:  (Cb, 1)    VMEM accumulator
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

# default tile sizes: time on lanes (multiple of 128), candidates on
# sublanes (multiple of 8), hosts streamed one sublane group per loop step
TB_T = 512     # max time-bins per block
TB_C = 64      # max candidates per block
TB_H = 8       # hosts per inner-loop step


def _kernel(u_ref, real_ref, pidle_ref, pmax_ref, r_ref, out_ref, *,
            n_t: int, n_h: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    hp, tb = u_ref.shape
    cb = r_ref.shape[0]
    r = jnp.broadcast_to(r_ref[...].astype(jnp.float32), (cb, tb))

    # sum_h u^r per candidate, as exp(r * log u) (Pallas/TPU has no f32
    # pow), accumulated host row by host row into a [Cb, Tb] tile
    def host_group(g, sr):
        h0 = pl.multiple_of(g * TB_H, TB_H)
        u = jnp.clip(u_ref[pl.ds(h0, TB_H), :].astype(jnp.float32), 0.0, 1.0)
        log_u = jnp.log(jnp.maximum(u, 1e-30))                  # [8, Tb]
        for k in range(TB_H):
            sr = sr + jnp.exp(r * log_u[k:k + 1, :])
        return sr

    sr = jax.lax.fori_loop(0, hp // TB_H, host_group,
                           jnp.zeros((cb, tb), jnp.float32))    # [Cb, Tb]
    u_all = jnp.clip(u_ref[...].astype(jnp.float32), 0.0, 1.0)
    s2 = jnp.sum(2.0 * u_all, axis=0, keepdims=True)            # [1, Tb]

    real = real_ref[...].astype(jnp.float32)                    # [1, Tb]
    p_idle = pidle_ref[...].astype(jnp.float32)                 # [Cb, 1]
    p_max = pmax_ref[...].astype(jnp.float32)                   # [Cb, 1]

    # valid-time mask for the ragged last block
    t_ids = ti * tb + jax.lax.broadcasted_iota(jnp.int32, (1, tb), 1)
    t_mask = (t_ids < n_t).astype(jnp.float32)                  # [1, Tb]

    # MAPE semantics shared with power.mape / the XLA oracle: |real| in the
    # denominator, zero-real bins masked out (the bin-count normalization
    # 100/n_nonzero is applied by the wrapper — n_nonzero is data-dependent
    # and candidate-independent, so the kernel only accumulates raw sums).
    nz_mask = (jnp.abs(real) > 1e-9).astype(jnp.float32)        # [1, Tb]
    sim = n_h * p_idle + (p_max - p_idle) * (s2 - sr)           # [Cb, Tb]
    rel = (jnp.abs((real - sim) / (jnp.abs(real) + 1e-9))
           * t_mask * nz_mask)                                  # [Cb, Tb]
    out_ref[...] += jnp.sum(rel, axis=1, keepdims=True)         # [Cb, 1]


@functools.partial(jax.jit, static_argnames=("interpret", "tb_t", "tb_c"))
def calib_mape_grid_pallas(
    u_th: Array,        # [T, H] float
    real_power: Array,  # [T]
    p_idle: Array,      # [C]
    p_max: Array,       # [C]
    r: Array,           # [C]
    *,
    interpret: bool = False,
    tb_t: int = TB_T,
    tb_c: int = TB_C,
) -> Array:             # [C] MAPE %
    t, h = u_th.shape
    c = r.shape[0]
    # shrink the tiles to the problem: a 144-bin history is one 256-lane
    # block, a 64-point r grid one 64-row candidate block
    tb_t = min(tb_t, pl.cdiv(t, 128) * 128)
    tb_c = min(tb_c, pl.cdiv(c, 8) * 8)
    hp = pl.cdiv(h, TB_H) * TB_H
    tp = pl.cdiv(t, tb_t) * tb_t
    cp = pl.cdiv(c, tb_c) * tb_c

    # padded hosts read u=0 (u^r ~ 1e-30, below an f32 ulp of any real sum)
    u = jnp.pad(u_th.astype(jnp.float32).T, ((0, hp - h), (0, tp - t)))
    real = jnp.pad(real_power.astype(jnp.float32), (0, tp - t),
                   constant_values=1.0)[None, :]           # avoid /0 in pad
    pad_c = (0, cp - c)
    pi = jnp.pad(p_idle.astype(jnp.float32), pad_c)[:, None]
    pm = jnp.pad(p_max.astype(jnp.float32), pad_c, constant_values=1.0)[:, None]
    rr = jnp.pad(r.astype(jnp.float32), pad_c, constant_values=1.0)[:, None]

    kernel = functools.partial(_kernel, n_t=t, n_h=h)
    out = pl.pallas_call(
        kernel,
        grid=(cp // tb_c, tp // tb_t),
        in_specs=[
            pl.BlockSpec((hp, tb_t), lambda ci, ti: (0, ti)),    # u^T
            pl.BlockSpec((1, tb_t), lambda ci, ti: (0, ti)),     # real
            pl.BlockSpec((tb_c, 1), lambda ci, ti: (ci, 0)),     # p_idle
            pl.BlockSpec((tb_c, 1), lambda ci, ti: (ci, 0)),     # p_max
            pl.BlockSpec((tb_c, 1), lambda ci, ti: (ci, 0)),     # r
        ],
        out_specs=pl.BlockSpec((tb_c, 1), lambda ci, ti: (ci, 0)),
        out_shape=jax.ShapeDtypeStruct((cp, 1), jnp.float32),
        interpret=interpret,
    )(u, real, pi, pm, rr)
    # normalization matches power.mape: mean over the *nonzero-real* bins
    # (zero-real bins carry no meaningful percentage error and were masked
    # inside the kernel); an all-zero window is undefined -> NaN for every
    # candidate, so the calibrator keeps its incumbent instead of "fitting".
    n_nz = jnp.sum(jnp.abs(real_power.astype(jnp.float32)) > 1e-9)
    scaled = out[:c, 0] * (100.0 / jnp.maximum(n_nz, 1))
    return jnp.where(n_nz > 0, scaled, jnp.nan)
