"""The placement scan's pending core releases: per-host slots, bit for bit
the table of releases by bin that they replaced.

A plain job holds at least one unit of one host until its release, so a
host has at most its capacity of plain releases pending.  The DES keeps
them in ``[K, hosts]`` slots (``desim._release_slots``), ``K`` the largest
capacity, instead of a ``[t_bins+1, hosts]`` table that a TPU relayouts
every bin under the scenario vmap.  These tests hold it to the table:

* the whole masked DES, with the table put back, gives the same
  ``job_start``, hosts, ``gang_blocked_bins``, ``u_th``, ``queue_len`` and
  ``running`` for every policy, with and without backfill, outages and
  drains, releases clipped to the horizon, a host holding ``K`` jobs,
  several placements on one host in one bin, a mixed fleet with gangs,
  read out unchunked and chunked, and vmapped over lanes;
* where two plain jobs land on one kill-host in one bin before its
  outage, one running into it and one ending before, on a plain fleet
  and a mixed gang fleet, the schedule is also the oracle's
  (``tests/reference.py``);
* the compiled what-if program keeps no array of a table's size in the
  scan, and gathers nothing per entry of the placement buffer there;
* ``K`` is checked where it is derived: a capacity above it is refused.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from reference import reference_gang_schedule, reference_u_th

from chipbench.spans import hlo_scopes
from repro.core import desim
from repro.core import scenarios as sc
from repro.runtime.fault import NEVER_BIN, HostFailure
from repro.traces.schema import DatacenterConfig, Workload
from repro.traces.surf import BINS_PER_DAY, SurfTraceSpec, make_surf22_like

DC = DatacenterConfig(num_hosts=16, cores_per_host=16)
T_BINS = int(1.25 * BINS_PER_DAY)       # 360: two read-out blocks, padded
POLICIES = ("first_fit", "best_fit", "worst_fit", "random_fit")
GANG_UNITS = np.asarray((8,) * 10 + (2,) * 10, np.int32)


def _install_table(monkeypatch, t_bins):
    """Put the releases back in a ``[t_bins+1, hosts]`` table by bin: row
    ``t`` is read at bin ``t``, row ``t_bins`` absorbs clipped releases."""
    monkeypatch.setattr(desim, "_release_slots", lambda slots, h: jnp.zeros(
        (t_bins + 1, h), jnp.int32))
    monkeypatch.setattr(desim, "_fire_releases",
                        lambda table, t: (table[t], table))
    monkeypatch.setattr(desim, "_bank_releases",
                        lambda table, host, end_bin, units:
                        table.at[end_bin, host].add(units))


@pytest.fixture(scope="module")
def surf():
    return make_surf22_like(SurfTraceSpec(days=1.25, seed=14), DC)


def _failures(seed, h, t_bins):
    """Outages and drains on about a third of the hosts."""
    rng = np.random.default_rng(seed)
    fs = np.where(rng.uniform(size=h) < 0.35, rng.integers(0, t_bins, h),
                  NEVER_BIN).astype(np.int32)
    fe = np.where(fs == NEVER_BIN, 0,
                  np.minimum(fs.astype(np.int64) + rng.integers(4, 90, h),
                             t_bins)).astype(np.int32)
    kill = rng.uniform(size=h) < 0.7
    return jnp.asarray(fs), jnp.asarray(fe), jnp.asarray(kill)


def _gang_workload(seed=5, jobs=300, t_bins=120):
    """Philly-shaped jobs of 1 to 64 GPUs: gangs of up to 8 servers."""
    rng = np.random.default_rng(seed)
    gpus = rng.choice([1, 2, 4, 8, 16, 32, 64], jobs,
                      p=[0.45, 0.15, 0.1, 0.1, 0.1, 0.06, 0.04])
    return Workload(
        np.sort(rng.integers(0, t_bins // 2, jobs)).astype(np.int32),
        rng.integers(1, 40, jobs).astype(np.int32), gpus.astype(np.int32),
        rng.uniform(0.1, 1.0, (jobs, 8)).astype(np.float32),
        np.ones(jobs, bool))


def _full_host_workload():
    """Two 4-core hosts: eight 1-core jobs at bin 0 fill both to exactly
    ``K = 4`` (four placements on each host in one bin), and twelve more
    wait for slots to come free and reuse them."""
    n = 20
    submit = np.asarray([0] * 8 + list(range(1, 13)), np.int32)
    dur = np.asarray([5, 9, 6, 12, 7, 8, 11, 10] + [3, 7, 2, 9, 4, 6, 5, 8,
                                                     3, 10, 2, 6], np.int32)
    return Workload(submit, dur, np.ones(n, np.int32),
                    np.full((n, 2), 0.5, np.float32), np.ones(n, bool))


KILL_GANG = 3


def _kill_bin(gang):
    """Host 0 fails hard over bins [10, 20).  At bin 2 two 2-unit jobs
    land on it, one ending at 7 and one running into the outage (killed
    at 10, its units back at 20); a later job is killed the same way, and
    the job behind them starts when those units come back.  On the mixed
    fleet (three 8-GPU servers, one 2-GPU) a gang takes two whole servers
    at bin 2, and the next gang, on all three, blocks bins until 20."""
    if gang:
        submit, dur, cores = ([2, 2, 2, 3, 3, 3, 4], [5, 15, 12, 12, 8, 3, 2],
                              [2, 2, 16, 24, 2, 2, 8])
        units, t_bins = np.asarray([8, 8, 8, 2], np.int32), 48
    else:
        submit, dur, cores = ([2, 2, 2, 3, 3, 4], [5, 15, 30, 4, 3, 2],
                              [2, 2, 4, 2, 4, 1])
        units, t_bins = np.asarray([4, 4], np.int32), 40
    n, h = len(submit), len(units)
    w = Workload(np.asarray(submit, np.int32), np.asarray(dur, np.int32),
                 np.asarray(cores, np.int32),
                 np.linspace(0.2, 0.9, n * 3, dtype=np.float32).reshape(n, 3),
                 np.ones(n, bool))
    fs = np.full(h, NEVER_BIN, np.int32)
    fe = np.zeros(h, np.int32)
    fs[0], fe[0] = 10, 20
    kill = np.arange(h) == 0
    return w, t_bins, units, h, (jnp.asarray(fs), jnp.asarray(fe),
                                 jnp.asarray(kill))


# (policy, backfill depth, failures, read-out chunked, kind)
CASES = {
    **{f"{p}-bf{d}": (p, d, False, False, "surf")
       for p in POLICIES for d in (0, 2)},
    "outages-unchunked": ("worst_fit", 2, True, False, "surf"),
    "outages-chunked": ("best_fit", 2, True, True, "surf"),
    "horizon": ("first_fit", 0, True, False, "horizon"),
    "full-host": ("worst_fit", 0, False, False, "full"),
    "gangs": ("best_fit", 2, True, False, "gang"),
    "kill-bin": ("first_fit", 0, True, False, "kill"),
    "kill-bin-gangs": ("first_fit", 2, True, False, "kill-gang"),
}


def _run(case, surf):
    """``(SimOutput, workload, t_bins, hosts)`` of one case, traced anew
    so that a patched release book counts."""
    policy, depth, fail, chunked, kind = CASES[case]
    w, t_bins, cores, h, gang = surf, T_BINS, DC.cores_per_host, 16, 1
    if kind == "horizon":
        # a horizon that ends while most jobs run: their releases clip
        t_bins = 72
    elif kind == "full":
        w, t_bins, cores, h = _full_host_workload(), 40, 4, 2
    elif kind == "gang":
        w, t_bins, cores, h, gang = _gang_workload(), 120, GANG_UNITS, 20, 8
    fs = fe = kill = None
    if kind in ("kill", "kill-gang"):
        w, t_bins, cores, h, (fs, fe, kill) = _kill_bin(kind == "kill-gang")
        gang = KILL_GANG if kind == "kill-gang" else 1
    elif fail:
        fs, fe, kill = _failures(len(case), h, t_bins)
    sim = jax.jit(lambda wl: desim.simulate_utilization_masked(
        wl, jnp.ones((h,), bool), cores, max_hosts=h, t_bins=t_bins,
        policy_id=desim.PLACEMENT_POLICIES[policy], backfill_depth=depth,
        max_backfill=depth, force_chunked_readout=chunked,
        fail_start=fs, fail_end=fe, fail_kill=kill, max_gang=gang))(w)
    return sim, w, t_bins, h


LEAVES = ("job_start", "job_host", "job_hosts", "gang_blocked_bins", "u_th",
          "queue_len", "running")


def _assert_bitwise(got, want):
    for leaf in LEAVES:
        a, b = getattr(got, leaf), getattr(want, leaf)
        assert (a is None) == (b is None), leaf
        if a is None:
            continue
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, leaf
        if a.dtype == np.float32:
            a, b = a.view(np.uint32), b.view(np.uint32)
        np.testing.assert_array_equal(a, b, err_msg=leaf)


def _concurrent(sim, w, t_bins, h):
    """``[t_bins, h]`` plain jobs running per host (kill rule aside)."""
    st = np.asarray(sim.job_start)
    host = np.asarray(sim.job_host)
    dur = np.maximum(np.asarray(w.duration_bins), 1)
    out = np.zeros((t_bins, h), np.int64)
    for s, hh, d in zip(st, host, dur):
        if s >= 0:
            out[s:min(s + d, t_bins), hh] += 1
    return out


@pytest.mark.parametrize("case", list(CASES))
def test_slots_equal_release_table(case, surf, monkeypatch):
    got, w, t_bins, h = _run(case, surf)
    _install_table(monkeypatch, t_bins)
    want, *_ = _run(case, surf)
    _assert_bitwise(got, want)
    # not vacuous: jobs started (all of a small case's) and the case's
    # condition holds
    st = np.asarray(got.job_start)
    assert (st >= 0).sum() >= min(10, st.size)
    kind = CASES[case][4]
    ends = st + np.maximum(np.asarray(w.duration_bins), 1)
    if kind == "horizon":
        assert ((st >= 0) & (ends > t_bins)).sum() >= 10
    if kind == "full":
        conc = _concurrent(got, w, t_bins, h)
        assert conc.max() == 4 and (conc == 4).sum() > 4
        assert (st >= 0).all()
    if kind in ("full", "surf"):
        # several placements on one host in one bin
        pairs = np.stack([st, np.asarray(got.job_host)], 1)[st >= 0]
        _, counts = np.unique(pairs, axis=0, return_counts=True)
        assert counts.max() >= 2
    if kind == "gang":
        hosts = np.asarray(got.job_hosts)
        assert ((hosts >= 0).sum(1) >= 2).sum() >= 5
        assert int(got.gang_blocked_bins) > 0
    if kind in ("kill", "kill-gang"):
        _assert_oracle_kill_bin(got, kind == "kill-gang")


def _assert_oracle_kill_bin(got, gang):
    """The schedule is the oracle's, and the case's bin is there: two
    plain jobs on kill-host 0 in one bin before its outage, one running
    into it and one ending before."""
    w, t_bins, units, h, fail = _kill_bin(gang)
    fs, fe, kill = (np.asarray(a).tolist() for a in fail)
    args = [np.asarray(a).tolist() for a in (
        w.submit_bin, w.duration_bins, w.cores, w.valid)]
    start, hosts, blocked = reference_gang_schedule(
        *args, num_hosts=h, cores_per_host=int(units.max()), t_bins=t_bins,
        policy="first_fit", backfill_depth=2 if gang else 0,
        fail_start=fs, fail_end=fe, fail_kill=kill,
        host_capacity=units.tolist(), max_gang=KILL_GANG if gang else 1)
    assert np.asarray(got.job_start).tolist() == start
    assert np.asarray(got.job_host).tolist() == [
        hs[0] if hs else -1 for hs in hosts]
    if gang:
        padded = [hs + [-1] * (KILL_GANG - len(hs)) for hs in hosts]
        assert np.asarray(got.job_hosts).tolist() == padded
        assert int(got.gang_blocked_bins) == blocked > 0
    u = reference_u_th(start, args[0], args[1], args[2],
                       np.asarray(w.util_levels).tolist(),
                       [hs[0] if hs else -1 for hs in hosts], num_hosts=h,
                       cores_per_host=int(units.max()), t_bins=t_bins,
                       fail_start=fs, fail_kill=kill, job_hosts=hosts,
                       host_capacity=units.tolist())
    np.testing.assert_allclose(np.asarray(got.u_th, np.float64), u,
                               rtol=2e-5, atol=1e-6)
    st, dur = np.asarray(start), args[1]
    on_0 = [i for i, hs in enumerate(hosts) if hs == [0] and st[i] == 2]
    assert fs[0] > 2
    assert any(st[i] + dur[i] > fs[0] for i in on_0)
    assert any(st[i] + dur[i] <= fs[0] for i in on_0)
    # the jobs behind them waited for the killed jobs' units at fail_end
    assert fe[0] in start


def _vmapped(w):
    lanes = [_failures(s, DC.num_hosts, T_BINS) for s in range(3)]
    fs, fe, kill = (jnp.stack(c) for c in zip(*lanes))
    return jax.jit(jax.vmap(
        lambda p, a, b, c: desim.simulate_utilization_masked(
            w, jnp.ones((DC.num_hosts,), bool), DC.cores_per_host,
            max_hosts=DC.num_hosts, t_bins=T_BINS, policy_id=p,
            backfill_depth=2, max_backfill=2, fail_start=a, fail_end=b,
            fail_kill=c)))(jnp.arange(3, dtype=jnp.int32), fs, fe, kill)


def test_vmapped_slots_equal_release_table(surf, monkeypatch):
    got = _vmapped(surf)
    _install_table(monkeypatch, T_BINS)
    want = _vmapped(surf)
    _assert_bitwise(got, want)
    assert (np.asarray(got.job_start) >= 0).sum() >= 30


_SHAPES = re.compile(r"\[([\d,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%(\S+) = (.*?) [\w-]+\(")


def test_scan_keeps_no_table_sized_array(surf):
    """No instruction in ``opendt.des_scan`` of the compiled SURF what-if
    program has an array of ``(t_bins+1) * max_hosts`` elements or more:
    one lane's table of releases by bin does not come back."""
    week = 7 * BINS_PER_DAY
    ss = sc.build_scenario_set(
        surf, DC, [sc.Scenario(name="a"),
                   sc.Scenario(name="b", policy="best_fit",
                               backfill_depth=2)],
        max_hosts=DC.num_hosts)
    assert ss.max_units == DC.cores_per_host
    text = jax.jit(functools.partial(
        sc._scenario_lanes, max_hosts=DC.num_hosts, t_bins=week,
        max_starts_per_bin=64, model="opendc", chunk=True)).lower(
        ss, None, None, None).compile().as_text()
    scopes = hlo_scopes(text)
    table = (week + 1) * DC.num_hosts
    in_scan, big = 0, {}
    for m in map(_INSTR.match, text.splitlines()):
        if not m or scopes.get(m.group(1)) != "opendt.des_scan":
            continue
        in_scan += 1
        n = max(int(np.prod([int(d) for d in s.split(",") if d]))
                for s in _SHAPES.findall(m.group(2)) or [""])
        if n >= table:
            big[m.group(1)] = n
    assert in_scan > 20, "expected the scan's instructions in the scope"
    assert not big, f"table-sized arrays in the scan: {big}"


def test_scan_gathers_nothing_per_buffer_entry(surf):
    """No ``gather`` in ``opendt.des_scan`` of the vmapped SURF what-if
    program, failures on, has ``S * max_starts_per_bin`` elements or
    more: each placement's release bin and units are banked where it is
    placed, and the buffer is not read against the job and host rows
    after the loop."""
    lanes, starts = 2, 64
    ss = sc.build_scenario_set(
        surf, DC, [sc.Scenario(name="a"),
                   sc.Scenario(name="b", policy="best_fit", backfill_depth=2,
                               failures=(HostFailure(3, 40, 90),))],
        max_hosts=DC.num_hosts)
    assert ss.has_failures
    text = jax.jit(functools.partial(
        sc._scenario_lanes, max_hosts=DC.num_hosts, t_bins=T_BINS,
        max_starts_per_bin=starts, model="opendc", chunk=True)).lower(
        ss, None, None, None).compile().as_text()
    scopes = hlo_scopes(text)
    gathers, big = 0, {}
    for m in map(_INSTR.match, text.splitlines()):
        if (not m or scopes.get(m.group(1)) != "opendt.des_scan"
                or not m.group(0).endswith(" gather(")):
            continue
        gathers += 1
        n = max(int(np.prod([int(d) for d in s.split(",") if d]))
                for s in _SHAPES.findall(m.group(2)) or [""])
        if n >= lanes * starts:
            big[m.group(1)] = n
    assert gathers > 0, "expected the scan's row reads as gathers"
    assert not big, f"gathers per buffer entry in the scan: {big}"


def test_capacity_above_max_units_is_refused(surf):
    with pytest.raises(ValueError, match="max_units=8"):
        sc.build_scenario_set(surf, DC, [sc.Scenario(name="a")],
                              max_units=8)
    with pytest.raises(ValueError, match="max_units=8"):
        sc.build_scenario_set(
            surf, DC, [sc.Scenario(name="a", cores_per_host=8),
                         sc.Scenario(name="b", cores_per_host=32)],
            max_units=16 // 2)
    mixed = DatacenterConfig(num_hosts=20, cores_per_host=8,
                             host_units=tuple(GANG_UNITS.tolist()))
    w = _gang_workload()
    assert sc.build_scenario_set(w, mixed, [sc.Scenario(name="a")],
                                 max_gang=8).max_units == 8
    with pytest.raises(ValueError, match="max_units=4"):
        sc.build_scenario_set(w, mixed, [sc.Scenario(name="a")],
                              max_gang=8, max_units=4)
    # pinned above the batch's largest capacity: accepted, same schedule
    pinned = sc.build_scenario_set(surf, DC, [sc.Scenario(name="a")],
                                   max_units=32)
    assert pinned.max_units == 32


def test_masked_des_checks_max_units(surf):
    mask = jnp.ones((DC.num_hosts,), bool)
    with pytest.raises(ValueError, match="max_units=8"):
        desim.simulate_utilization_masked(
            surf, mask, np.int32(16), max_hosts=DC.num_hosts,
            t_bins=T_BINS, max_units=8)
    with pytest.raises(ValueError, match="needs max_units"):
        jax.jit(lambda c: desim.simulate_utilization_masked(
            surf, mask, c, max_hosts=DC.num_hosts, t_bins=T_BINS))(
            jnp.int32(16))
