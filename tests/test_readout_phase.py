"""The read-out's phase lookup: compares and selects, bit for bit a gather.

A job ``x`` bins after its start is in phase ``clip(x * P // du, 0, P-1)``
of its ``P`` utilization levels.  The read-out picks that level with a
static chain of compares against per-job thresholds
(``desim._phase_lookup``) instead of a per-element gather, which runs
serially on a TPU.  These tests hold it to the gather it replaced:

* the lookup alone returns the same f32 bits for every ``P``, duration
  and offset, negative offsets (the ``tt = -1`` padding) and offsets past
  the job's end included;
* a whole DES with failures, chunked and not, and vmapped over lanes,
  gives the same ``u_th``, ``queue_len`` and ``running`` as the same DES
  with the gather put back;
* the compiled what-if program has no gather of ``[jobs, bins]`` size left
  in the read-out's scope.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.spans import hlo_scopes
from repro.core import desim
from repro.core import scenarios as sc
from repro.core.power import PowerParams
from repro.runtime.fault import DEGRADED, NEVER_BIN, HostFailure
from repro.traces.schema import DatacenterConfig
from repro.traces.surf import BINS_PER_DAY, SurfTraceSpec, make_surf22_like


def _gather_lookup(util_levels, du):
    """The lookup as it was: the phase index, then a per-element gather."""
    n_p = util_levels.shape[-1]

    def level_at(x):
        phase = jnp.clip(x * n_p // jnp.maximum(du, 1), 0, n_p - 1)
        return jnp.take_along_axis(util_levels, phase, axis=1)

    return level_at


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


def _durations(n_p, kind, rng):
    """``[J]`` durations (bins, >= 1) of one kind relative to ``P``."""
    if kind == "below_p":
        return np.arange(1, n_p, dtype=np.int32)
    if kind == "equal_p":
        return np.array([n_p], np.int32)
    if kind == "not_multiple":
        d = np.array([n_p + 1, 2 * n_p + 1, 5 * n_p - 1, 97, 2017], np.int32)
        return d[d % n_p != 0]
    if kind == "multiple":
        return np.array([2 * n_p, 36 * n_p, 252 * n_p], np.int32)
    return rng.integers(1, 3001, 64).astype(np.int32)          # random


_LOOKUP_CASES = [(p, kind) for p in (1, 2, 3, 8)
                 for kind in ("below_p", "equal_p", "not_multiple",
                              "multiple", "random")
                 if p > 1 or kind not in ("below_p", "not_multiple")]


@pytest.mark.parametrize("n_p,kind", _LOOKUP_CASES,
                         ids=[f"P{p}-{k}" for p, k in _LOOKUP_CASES])
def test_select_chain_equals_gather(n_p, kind):
    rng = np.random.default_rng(1000 * n_p + len(kind))
    du = _durations(n_p, kind, rng)
    j = du.shape[0]
    # distinct levels per phase, so a wrong pick shows in the bits
    levels = rng.uniform(0.0, 1.2, (j, n_p)).astype(np.float32)
    st = rng.integers(0, 2016, j).astype(np.int32)
    # every offset from before the start through past the end, plus the
    # offset of the tt = -1 padding (x = -1 - st) and far past the end
    span = int(du.max()) + 8
    x = np.arange(-6, span, dtype=np.int32)[None, :].repeat(j, 0)
    x = np.concatenate([x, (-1 - st)[:, None], (du + 4000)[:, None]],
                       axis=1)
    want = _gather_lookup(jnp.asarray(levels), jnp.asarray(du)[:, None])(
        jnp.asarray(x))
    got = jax.jit(lambda lv, d, xx: desim._phase_lookup(lv, d)(xx))(
        jnp.asarray(levels), jnp.asarray(du)[:, None], jnp.asarray(x))
    assert got.shape == want.shape == x.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))


DC = DatacenterConfig(num_hosts=16, cores_per_host=16)
T_BINS = int(1.25 * BINS_PER_DAY)       # 360: two read-out blocks, padded


@pytest.fixture(scope="module")
def workload():
    return make_surf22_like(SurfTraceSpec(days=1.25, seed=14), DC)


def _failures(seed):
    """Outages and drains on a third of the hosts, the rest never fail."""
    rng = np.random.default_rng(seed)
    h = DC.num_hosts
    fs = np.where(rng.uniform(size=h) < 0.35, rng.integers(0, T_BINS, h),
                  NEVER_BIN).astype(np.int32)
    fe = np.where(fs == NEVER_BIN, 0,
                  np.minimum(fs.astype(np.int64) + rng.integers(4, 90, h),
                             T_BINS)).astype(np.int32)
    kill = rng.uniform(size=h) < 0.7
    return jnp.asarray(fs), jnp.asarray(fe), jnp.asarray(kill)


def _run_des(workload, mode):
    """``SimOutput`` of one DES, traced anew so a patched lookup counts."""
    des = functools.partial(
        desim.simulate_utilization_masked,
        max_hosts=DC.num_hosts, t_bins=T_BINS, max_backfill=2,
        force_chunked_readout=mode != "unchunked")
    mask = jnp.ones((DC.num_hosts,), bool)
    if mode != "vmapped":
        fs, fe, kill = _failures(3)
        return jax.jit(lambda w: des(
            w, mask, DC.cores_per_host, policy_id=2, backfill_depth=2,
            fail_start=fs, fail_end=fe, fail_kill=kill))(workload)
    lanes = [_failures(s) for s in range(3)]
    fs, fe, kill = (jnp.stack(c) for c in zip(*lanes))
    return jax.jit(jax.vmap(
        lambda p, a, b, c: des(workload, mask, DC.cores_per_host,
                               policy_id=p, backfill_depth=2,
                               fail_start=a, fail_end=b, fail_kill=c)))(
        jnp.arange(3, dtype=jnp.int32), fs, fe, kill)


@pytest.mark.parametrize("mode", ["unchunked", "chunked", "vmapped"])
def test_des_equals_gather_readout(workload, mode, monkeypatch):
    got = _run_des(workload, mode)
    calls = []

    def counted(util_levels, du):
        calls.append(1)
        return _gather_lookup(util_levels, du)

    monkeypatch.setattr(desim, "_phase_lookup", counted)
    want = _run_des(workload, mode)
    assert calls, "the reference lookup was not traced"
    for leaf in ("u_th", "queue_len", "running", "job_start", "job_host"):
        a, b = np.asarray(getattr(got, leaf)), np.asarray(getattr(want, leaf))
        assert a.dtype == b.dtype and a.shape == b.shape, leaf
        if a.dtype == np.float32:
            a, b = _bits(a), _bits(b)
        np.testing.assert_array_equal(a, b, err_msg=leaf)
    # not vacuous: jobs started and kept hosts busy
    assert np.asarray(got.u_th).max() > 0
    assert (np.asarray(got.job_start) >= 0).sum() > 50


_GATHER = re.compile(r"^\s*(?:ROOT\s+)?%(\S+) = \w+\[([\d,]*)\]\S* gather\(")


def test_expand_has_no_per_element_gather(workload):
    """No gather in ``opendt.des_expand`` of J x B elements or more: the
    per-element level lookup does not come back.  The ``[J]`` gathers of
    the failure windows per job stay allowed."""
    outage = (HostFailure(host=2, start_bin=40, end_bin=120),
              HostFailure(host=5, start_bin=200, end_bin=260, kind=DEGRADED))
    ss = sc.build_scenario_set(
        workload, DC, [sc.Scenario(name="a"),
                       sc.Scenario(name="b", policy="best_fit",
                                   failures=outage)],
        PowerParams(), max_hosts=DC.num_hosts)
    n_jobs = int(ss.workload.submit_bin.shape[-1])
    for chunk in (False, True):
        compiled = jax.jit(functools.partial(
            sc._scenario_lanes, max_hosts=DC.num_hosts, t_bins=T_BINS,
            max_starts_per_bin=64, model="opendc", chunk=chunk)).lower(
            ss, None, None, None).compile()
        text = compiled.as_text()
        scopes = hlo_scopes(text)
        block = min(T_BINS, desim._READOUT_BLOCK) if chunk else T_BINS
        gathers = {m.group(1): int(np.prod([int(d) for d in m.group(2)
                                            .split(",") if d]))
                   for m in map(_GATHER.match, text.splitlines()) if m}
        in_expand = {g: n for g, n in gathers.items()
                     if scopes.get(g) == "opendt.des_expand"}
        assert in_expand, "expected the [J] failure gathers in the scope"
        big = {g: n for g, n in in_expand.items() if n >= n_jobs * block}
        assert not big, f"chunk={chunk}: per-element gathers {big}"
