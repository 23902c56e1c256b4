"""Vectorized discrete-event datacenter simulation.

OpenDC — the simulator at the paper's core (FR2) — is an event-queue DES.
Event queues are pointer-chasing and data-dependent: hostile to TPUs and to
XLA.  Since the paper only ever *reads out* the simulation at the
industry-standard 5-minute granularity (§3.3), we adapt the simulator to the
hardware instead of porting the algorithm: a **dense, fixed-timestep,
time-marching simulation** whose state is tensors over ``[hosts]`` and
``[jobs]``, advanced by ``jax.lax.scan`` over 5-minute bins.

Event-driven semantics preserved at bin granularity:
  * job completion releases cores at the bin where ``start + duration`` falls;
  * FCFS placement with a bounded while-loop of placement attempts per bin
    (head-of-line blocking, like OpenDC's default scheduler), optionally
    relaxed by a bounded backfill window (see below);
  * per-job piecewise utilization profiles (OpenDC "fragments").

The *placement policy* — which host a job lands on, and whether queued
successors may jump a blocked head — is a **traced scenario knob**, not a
code path: host selection goes through a branchless ``policy_id``-indexed
score kernel (first-fit / best-fit / worst-fit / random-fit) and a traced
``backfill_depth`` bounds how many blocked-queue successors may start ahead
of the head.  Because both knobs are int32 scalars, the whole simulation
stays ``jax.vmap``-able over a scenario axis and one jitted program sweeps
schedulers *and* topologies together (see :mod:`repro.core.scenarios`).

Everything is one jitted program — NFR2's "7 days in under an hour" becomes
"7 days in well under a second" on a single CPU core (see benchmarks).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.power import (
    PowerParams,
    carbon_gco2,
    datacenter_power,
    energy_kwh,
)
from repro.traces.schema import SAMPLE_SECONDS, DatacenterConfig, Workload

Array = jax.Array

#: time-axis block size of the post-scan read-out — bounds the dense
#: [jobs, bins] intermediates at O(jobs * block) per scenario (one day of
#: 5-minute bins per block).
_READOUT_BLOCK = 288

#: below this many [jobs, bins] elements per scenario the read-out runs in a
#: single pass (no lax.map): the intermediates are small and the blocked
#: scan only adds compile time.
_READOUT_CHUNK_THRESHOLD = 4_000_000

# -- placement policies -------------------------------------------------------
# Policy ids are *traced* int32 scalars: a scenario batch carries one per lane
# and the score kernel indexes a stacked [4, hosts] score table, so sweeping
# schedulers never retraces or recompiles.

FIRST_FIT = 0   #: lowest-indexed host that fits (packs the host prefix)
BEST_FIT = 1    #: fitting host with the fewest free cores (tightest pack)
WORST_FIT = 2   #: fitting host with the most free cores (spreads load;
                #: OpenDC's default mem/core-aware weigher — the seed behavior)
RANDOM_FIT = 3  #: deterministic pseudo-random fitting host (hash of
                #: (bin, placement#, host) — reproducible, seed-free)

#: name -> traced policy id, the scenario-facing vocabulary
PLACEMENT_POLICIES = {
    "first_fit": FIRST_FIT,
    "best_fit": BEST_FIT,
    "worst_fit": WORST_FIT,
    "random_fit": RANDOM_FIT,
}

#: id -> name (summaries / examples print this)
POLICY_NAMES = {v: k for k, v in PLACEMENT_POLICIES.items()}

#: bias making best-fit scores positive: scores must stay above the -1
#: "does not fit" sentinel, and free-core counts are far below 2**24.
_BEST_FIT_BIAS = 1 << 24


def resolve_policy(policy: "str | int | None") -> int:
    """Map a policy name (or id) to its int id; ``None`` -> worst-fit.

    >>> resolve_policy("first_fit")
    0
    >>> resolve_policy(None) == PLACEMENT_POLICIES["worst_fit"]
    True
    """
    if policy is None:
        return WORST_FIT
    if isinstance(policy, str):
        try:
            return PLACEMENT_POLICIES[policy]
        except KeyError:
            raise ValueError(
                f"unknown placement policy {policy!r}; "
                f"one of {sorted(PLACEMENT_POLICIES)}") from None
    p = int(policy)
    if p not in POLICY_NAMES:
        raise ValueError(f"policy id {p} not in {sorted(POLICY_NAMES)}")
    return p


def _hash_scores(host_idx: Array, t: Array, salt: Array) -> Array:
    """Deterministic per-host pseudo-random scores for RANDOM_FIT.

    A seed-free integer mix of (bin, placement-count-within-bin, host index):
    reproducible across runs and replicable in plain numpy (the test
    reference), with no PRNG key threaded through the scan carry.
    """
    x = (host_idx.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         ^ t.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
         ^ salt.astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D))
    x = (x ^ (x >> jnp.uint32(16))) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> jnp.uint32(15))) * jnp.uint32(0x846CA68B)
    x = x ^ (x >> jnp.uint32(16))
    return (x & jnp.uint32(0x7FFFFF)).astype(jnp.int32)


def _policy_score(free: Array, policy_id: Array, t: Array, salt: Array,
                  max_hosts: int) -> Array:
    """Per-host int32 score of the *traced* ``policy_id`` (all >= 0).

    Builds the [4, max_hosts] score table and gathers the policy's row, so
    the four policies share one program.
    """
    idx = jnp.arange(max_hosts, dtype=jnp.int32)
    scores = jnp.stack([
        max_hosts - idx,                                    # FIRST_FIT
        _BEST_FIT_BIAS - jnp.minimum(free, _BEST_FIT_BIAS - 1),  # BEST_FIT
        free,                                               # WORST_FIT
        _hash_scores(idx, t, salt),                         # RANDOM_FIT
    ])
    return scores[jnp.clip(policy_id, 0, len(PLACEMENT_POLICIES) - 1)]


def _policy_host(free: Array, fits: Array, policy_id: Array,
                 t: Array, salt: Array, max_hosts: int) -> Array:
    """Branchless host selection: argmax of a policy-indexed score.

    The score (:func:`_policy_score`) is >= 0, so the -1 "does not fit"
    sentinel always loses.  Ties break to the lowest host index (argmax
    returns the first maximum), which makes WORST_FIT bit-identical to the
    pre-policy-kernel scheduler ``argmax(where(fits, free, -1))``.
    """
    score = _policy_score(free, policy_id, t, salt, max_hosts)
    return jnp.argmax(jnp.where(fits, score, -1))


# -- pending core releases ----------------------------------------------------
# A plain job holds at least one unit of one host until its release, and the
# units a host holds never exceed its capacity, so a host has at most
# `capacity` plain releases pending.  Each sits in a slot of its own:
# [slots, hosts] arrays of the bin its units come back (-1 = empty) and of
# its units, with `slots` the largest capacity.  A release clipped to the
# horizon never fires and keeps its slot, as its units stay held.  A
# [t_bins+1, hosts] table of releases by bin is exact too, but under the
# scenario vmap its per-bin scatter makes a TPU relayout the whole table
# every bin.

def _release_slots(slots: int, max_hosts: int) -> tuple[Array, Array]:
    """Empty release slots: ``(rel_bin, rel_units)``, each
    ``[slots, max_hosts]`` int32."""
    return (jnp.full((slots, max_hosts), -1, jnp.int32),
            jnp.zeros((slots, max_hosts), jnp.int32))


def _fire_releases(releases, t: Array):
    """``(units back per host [H], releases)``: the slots due at bin
    ``t`` return their units and are emptied."""
    rel_bin, rel_units = releases
    fire = rel_bin == t                                             # [K, H]
    return (jnp.sum(jnp.where(fire, rel_units, 0), axis=0),
            (jnp.where(fire, -1, rel_bin), rel_units))


def _bank_releases(releases, host: Array, end_bin: Array, units: Array):
    """Bank a bin's placements ``[B]``: entry ``i`` takes the
    ``r_i``-th empty slot of ``host[i]``, ``r_i`` the number of earlier
    entries on that host, and holds ``units[i]`` until ``end_bin[i]``.  An
    entry of 0 units (an unused buffer entry, a gang) takes none.

    Under the scenario vmap a scatter would make a TPU relayout the slots
    every bin, so both moves between entries and hosts are matrix
    products with one-hot operands: each sum has at most one nonzero term,
    an integer below 2**24, so the f32 products at HIGHEST precision are
    exact.
    """
    rel_bin, rel_units = releases
    n_slots, n_hosts = rel_bin.shape
    exact = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    take = units > 0                                                # [B]
    b = jnp.arange(host.shape[0])
    rank = jnp.sum((host[None, :] == host[:, None]) & take[None, :]
                   & (b[None, :] < b[:, None]), axis=1)             # [B]
    on_host = ((host[:, None] == jnp.arange(n_hosts, dtype=host.dtype))
               & take[:, None]).astype(jnp.float32)                # [B, H]
    # each entry's host column of empty slots; it takes the one that
    # brings the count of empties to rank + 1
    col = exact("bh,kh->bk", on_host,
                (rel_bin < 0).astype(jnp.float32)).astype(jnp.int32)
    pick = ((col > 0) & (jnp.cumsum(col, axis=1) == rank[:, None] + 1)
            & take[:, None]).astype(jnp.float32)                   # [B, K]
    vals = jnp.stack([end_bin, units]).astype(jnp.float32)          # [2, B]
    new_bin, new_units = exact("vb,bk,bh->vkh", vals, pick,
                               on_host).astype(jnp.int32)
    got = new_units > 0
    return (jnp.where(got, new_bin, rel_bin),
            jnp.where(got, new_units, rel_units))


def _put(buf: Array, n: Array, v: Array) -> Array:
    """``buf`` with entry ``n`` of its leading axis set to ``v``, by a
    select: under the scenario vmap ``buf.at[n].set(v)`` with a per-lane
    ``n`` is a scatter."""
    at = jnp.arange(buf.shape[0]) == n
    return jnp.where(at.reshape((-1,) + (1,) * (buf.ndim - 1)), v, buf)


@dataclasses.dataclass(frozen=True)
class SimOutput:
    """Dense simulation read-out at 5-minute granularity.

    Attributes:
      u_th: ``[T, H]`` per-host utilization in [0, 1].
      queue_len: ``[T]`` jobs submitted but not yet started.
      running: ``[T]`` jobs running.
      job_start: ``[J]`` assigned start bin (-1 if never started).
      job_host: ``[J]`` assigned host (-1 if never started); a gang's
        first host.
      job_hosts: ``[J, max_gang]`` every host of each job, ``-1``-padded,
        or ``None`` when gangs are compiled out (``max_gang = 1``).
      gang_blocked_bins: scalar int32, bins whose placement stopped at a
        gang head that found too few whole free servers; ``None`` when
        gangs are compiled out.
    """

    u_th: Array
    queue_len: Array
    running: Array
    job_start: Array
    job_host: Array
    job_hosts: Array | None = None
    gang_blocked_bins: Array | None = None


jax.tree_util.register_pytree_node(
    SimOutput,
    lambda s: ((s.u_th, s.queue_len, s.running, s.job_start, s.job_host,
                s.job_hosts, s.gang_blocked_bins), None),
    lambda _, c: SimOutput(*c),
)


def _phase_lookup(util_levels: Array, du: Array):
    """``level_at(x)``: each job's utilization level ``x`` bins after its
    start, for ``util_levels [J, P]`` and durations ``du [J, 1]`` (>= 1).

    A job is in phase ``clip(x * P // du, 0, P - 1)``.  For integer ``x``
    and ``du >= 1`` that phase is ``>= p`` exactly when
    ``x >= ceil(p * du / P)``, negative ``x`` (the ``tt = -1`` padding)
    included, so the per-job thresholds ``[J, P-1]`` are computed once and
    the lookup is a static chain of ``P - 1`` compares and selects: vector
    work that returns the same f32 element a per-element gather would
    (and a batched gather runs serially on a TPU).  Its cost grows with
    the static ``P`` (8 for SURF-22); the chain stays cheaper than the
    gather until ``P`` is in the hundreds, so every caller shares it.
    """
    n_p = util_levels.shape[-1]
    p_up = jnp.arange(1, n_p, dtype=jnp.int32)                      # [P-1]
    # ceil(p * du / P) as p * (du // P) + ceil(p * (du % P) / P): no
    # product can overflow int32
    thresh = p_up * (du // n_p) + (p_up * (du % n_p) + n_p - 1) // n_p

    def level_at(x):
        u = util_levels[:, :1]
        for p in range(1, n_p):
            u = jnp.where(x >= thresh[:, p - 1:p], util_levels[:, p:p + 1], u)
        return jnp.broadcast_to(u, x.shape)

    return level_at


def simulate_utilization_masked(
    w: Workload,
    host_mask: Array,
    cores_per_host: Array,
    *,
    max_hosts: int,
    t_bins: int,
    max_starts_per_bin: int = 64,
    policy_id: "Array | int | None" = None,
    backfill_depth: "Array | int | None" = None,
    max_backfill: int = 0,
    force_chunked_readout: bool = False,
    fail_start: "Array | None" = None,
    fail_end: "Array | None" = None,
    fail_kill: "Array | None" = None,
    max_gang: int = 1,
    max_units: "int | None" = None,
) -> SimOutput:
    """Masked-host-axis DES core (trace-level; callers jit/vmap it).

    The host axis is padded to a static ``max_hosts``; ``host_mask [max_hosts]``
    marks the active hosts and ``cores_per_host`` is a *traced* int32 scalar,
    or a ``[max_hosts]`` row of per-host capacities for a fleet of mixed
    server sizes (units: cores, or GPUs).
    Inactive hosts start with 0 free cores and are excluded from placement, so
    they never run jobs and report 0 utilization.  Because every argument that
    varies between what-if candidates (mask, cores, workload, **policy**) is a
    tensor, the whole simulation is ``jax.vmap``-able over a scenario axis —
    the batched engine in :mod:`repro.core.scenarios` is exactly that vmap.

    Scheduling knobs (both *traced* int32 scalars, hence scenario axes):

    ``policy_id``
        Which host a placeable job lands on — one of
        :data:`PLACEMENT_POLICIES` (``None`` -> :data:`WORST_FIT`, the
        seed scheduler).  Selection is a branchless score-table gather
        (:func:`_policy_host`), so all four policies share one program.
    ``backfill_depth``
        When the FCFS head job is submitted but no host fits it, up to
        ``backfill_depth`` of its queued successors (submitted, valid, not
        already started) may start ahead of it, scanned in queue order.
        0 (the default) is strict head-of-line blocking.  Backfill never
        runs while the head is merely unsubmitted — jobs cannot start
        before jobs that have not arrived yet.

    ``max_backfill`` is the *static* window the traced depth is clipped to;
    leaving it 0 compiles the backfill machinery out entirely, making the
    default path structurally identical to the pre-policy-kernel scheduler.

    Failure schedules (``fail_start`` / ``fail_end`` / ``fail_kill``, all
    ``[max_hosts]``, together or not at all) add a *time-varying* layer to
    the host mask: during ``[fail_start[h], fail_end[h])`` host ``h``
    accepts no new placements, and if ``fail_kill[h]`` its running jobs
    are killed at the window start (cores return when the host does, at
    ``fail_end``; killed jobs are not re-queued) — a hard outage.  With
    ``fail_kill[h]`` false the host merely drains (running jobs finish
    normally).  Hosts that never fail carry the sentinel start
    ``np.iinfo(int32).max`` (see :func:`repro.runtime.fault.failure_arrays`),
    making every window comparison false — a disabled lane in a mixed
    batch computes bit-for-bit the no-failure schedule.  Presence of the
    arrays is *structural* (a Python-level ``is not None``), so the
    default program is unchanged when the axis is off.

    Gang jobs (static ``max_gang > 1``): a job asking for more than
    ``unit`` units, the largest active host's capacity, is gang-scheduled.
    It takes ``n = ceil(cores / unit)`` whole free ``unit``-hosts that are
    online, all at once or not at all: the top ``n`` of the policy's score
    (ties to the lowest index; :func:`jax.lax.top_k`), each host held whole
    and running ``cores / n`` units of the job.  A job wider than
    ``max_gang`` hosts never fits, as a single-host job wider than every
    host never does.  Backfill candidates may be gangs.  If any host of a
    running gang has an outage (``fail_kill``), the whole job dies at the
    first outage start: its healthy hosts come back at that bin, the
    outage host at its ``fail_end``.  ``max_gang = 1`` compiles the gang
    machinery out, as ``max_backfill = 0`` does backfill, and then
    ``job_hosts`` and ``gang_blocked_bins`` are ``None``.

    ``max_units`` is the largest host capacity, static: a plain job holds
    at least one unit of one host until its release, so a host has at
    most that many plain releases pending, each kept in a slot of its own.
    ``None`` derives it from a concrete ``cores_per_host``; a traced one
    needs it (the batch engine carries it from
    :func:`repro.core.scenarios.build_scenario_set`).

    Placement (the event-driven part) is a bounded policy-kernel loop inside
    the scan body; utilization accumulation is a segment-sum scatter over
    host assignments.  Utilization is *independent of power-model
    parameters* — the structural fact the Self-Calibrator exploits (see
    calibrate.py).
    """
    if not 0 <= max_backfill <= 31:
        # the skip bitmask is uint32 and bit max_backfill must be addressable
        raise ValueError(f"max_backfill must be in [0, 31], got {max_backfill}")
    if max_gang < 1:
        raise ValueError(f"max_gang must be >= 1, got {max_gang}")
    gang = max_gang > 1
    if isinstance(cores_per_host, jax.core.Tracer):
        if max_units is None:
            raise ValueError("a traced cores_per_host needs max_units, "
                             "the largest host capacity")
    else:
        # tracecheck: disable=TC002 — concrete here: Tracer excluded above
        widest_host = int(np.max(np.asarray(cores_per_host)))
        if max_units is None:
            max_units = widest_host
        elif widest_host > max_units:
            raise ValueError(f"a host holds {widest_host} units > "
                             f"max_units={max_units}")
    slots = max(int(max_units), 1)
    j = w.num_jobs
    host_mask = jnp.asarray(host_mask, jnp.bool_)
    cores_per_host = jnp.asarray(cores_per_host, jnp.int32)
    policy_id = jnp.asarray(
        WORST_FIT if policy_id is None else policy_id, jnp.int32)
    backfill_depth = jnp.asarray(
        0 if backfill_depth is None else backfill_depth, jnp.int32)
    depth = jnp.minimum(backfill_depth, max_backfill)
    if (fail_start is None) != (fail_end is None) or \
            (fail_start is None) != (fail_kill is None):
        raise ValueError(
            "fail_start/fail_end/fail_kill must be supplied together")
    if fail_start is not None:
        fail_start = jnp.asarray(fail_start, jnp.int32)
        fail_end = jnp.asarray(fail_end, jnp.int32)
        fail_kill = jnp.asarray(fail_kill, jnp.bool_)

    submit = w.submit_bin
    dur = jnp.maximum(w.duration_bins, 1)
    cores = w.cores
    valid = w.valid
    if gang:
        # the gang unit: the largest active host.  A job above it needs
        # n_host whole unit-hosts; every other job one host.
        unit = jnp.maximum(
            jnp.max(jnp.where(host_mask, cores_per_host, 0)), 1)
        is_gang = cores > unit                                      # [J]
        n_host = jnp.where(is_gang, (cores + unit - 1) // unit, 1)  # [J]
        gang_ok = n_host <= max_gang

    # The scan carries *placement state only*: which job starts where/when,
    # free cores, the pending plain-job releases (`slots` per host, see
    # _release_slots), and a skip bitmask of backfilled jobs ahead of the
    # FCFS pointer.  Everything read out per bin (utilization field, queue
    # depth, running count) is reconstructed vectorized AFTER the scan from
    # job_start — per-bin O(jobs) passes inside the scan would dominate the
    # runtime and, under the scenario vmap, multiply by S with no
    # amortization.
    init = dict(
        free=jnp.where(host_mask, cores_per_host, 0).astype(jnp.int32),
        job_host=jnp.full((j,), -1, jnp.int32),
        job_start=jnp.full((j,), -1, jnp.int32),
        next_job=jnp.asarray(0, jnp.int32),
        # bit d set <=> job next_job+d already started via backfill.  Bit 0 is
        # never set at rest: every pointer advance immediately consumes the
        # trailing run of set bits, so the head is always an unstarted job.
        skip=jnp.asarray(0, jnp.uint32),
        releases=_release_slots(slots, max_hosts),
    )
    if gang:
        # a gang's hosts replace the one host per job.  A gang holds each
        # of its hosts whole, so a host has at most one gang release
        # pending, kept per host (the bin its units come back, -1 = none)
        # and not in the slots.
        del init["job_host"]
        init["job_hosts"] = jnp.full((j, max_gang), -1, jnp.int32)
        init["gang_back"] = jnp.full((max_hosts,), -1, jnp.int32)
        init["gang_blocked"] = jnp.asarray(0, jnp.int32)

    def head_ready(next_job, blocked, t):
        """Is the FCFS head job submittable at bin t (and are we unblocked)?"""
        jid = jnp.minimum(next_job, j - 1)
        return ((next_job < j) & (submit[jid] <= t) & valid[jid]
                & jnp.logical_not(blocked))

    def consume_skips(next_job, skip):
        """Advance the FCFS pointer past already-backfilled (started) jobs."""
        # trailing-ones count: first zero bit index.  Backfill sets bits
        # 1..max_backfill only, so a zero always exists in this window.
        bits = ((skip >> jnp.arange(max_backfill + 2, dtype=jnp.uint32))
                & jnp.uint32(1))
        k = jnp.argmin(bits).astype(jnp.uint32)
        return next_job + k.astype(jnp.int32), skip >> k

    # Placement runs in a while_loop with a deliberately *small* carry:
    # under vmap, the batched while_loop body re-runs for every lane until
    # all lanes are done and select-freezes every carry leaf per iteration,
    # so carrying the [jobs]-sized state here would cost O(S * jobs) per
    # attempt.  Instead each attempt records its job, host(s), release bin
    # and units into [max_starts_per_bin] buffers, written by selects (a
    # per-lane `.at[n].set` is a scatter under the vmap); the buffers go
    # into the scan carry once per bin.  Every iteration either places
    # exactly one job or sets `blocked` (ending the bin), so the loop is
    # bounded by max_starts_per_bin placements.
    def place_one(carry):
        (free, next_job, skip, blocked, t, n, buf_jid, buf_host, buf_end,
         buf_units) = carry[:10]
        # failed hosts (outage or drain) accept no new placements during
        # their window; sentinel starts make this the plain mask.
        if fail_start is not None:
            online = host_mask & jnp.logical_not(
                (fail_start <= t) & (t < fail_end))
        else:
            online = host_mask
        jid_h = jnp.minimum(next_job, j - 1)
        # re-checked inside the body: finished vmap lanes degrade to no-ops.
        eligible = head_ready(next_job, blocked, t)
        head_fits = jnp.any((free >= cores[jid_h]) & online)
        if gang:
            # whole free unit-hosts: free == capacity == unit
            whole = (free >= unit) & online
            n_whole = jnp.sum(whole.astype(jnp.int32))

            def fits_job(jid, fits_one):
                """A gang needs enough whole hosts; a job one host."""
                return jnp.where(is_gang[jid],
                                 gang_ok[jid] & (n_host[jid] <= n_whole),
                                 fits_one)

            head_fits = fits_job(jid_h, head_fits)
        place_head = eligible & head_fits

        if max_backfill > 0:
            # head is submitted but capacity-blocked: scan the next
            # `depth` queue positions in order for the first startable job.
            d_off = jnp.arange(1, max_backfill + 1, dtype=jnp.int32)  # [K]
            cand = next_job + d_off
            jid_c = jnp.minimum(cand, j - 1)
            already = ((skip >> d_off.astype(jnp.uint32)) & 1).astype(bool)
            elig_c = ((cand < j) & (submit[jid_c] <= t) & valid[jid_c]
                      & jnp.logical_not(already) & (d_off <= depth))
            fits_c = ((free[None, :] >= cores[jid_c][:, None])
                      & online[None, :])                             # [K, H]
            fits_any = jnp.any(fits_c, axis=1)                       # [K]
            if gang:
                fits_any = fits_job(jid_c, fits_any)
            startable = elig_c & fits_any                            # [K]
            any_bf = jnp.any(startable)
            d_sel = jnp.argmax(startable)        # first startable offset - 1
            place_bf = eligible & jnp.logical_not(head_fits) & any_bf
            jid = jnp.where(place_head, jid_h, jid_c[d_sel])
        else:
            place_bf = jnp.asarray(False)
            jid = jid_h

        need = cores[jid]
        end_nom = t + dur[jid]
        fits = (free >= need) & online
        do_place = place_head | place_bf
        host_idx = jnp.arange(max_hosts, dtype=jnp.int32)
        if gang:
            with jax.named_scope("opendt.gang_select"):
                # the top n_host of the policy's score among whole hosts
                # (one host among fitting ones for a plain job); top_k
                # breaks ties to the lowest index, as argmax does.
                g = is_gang[jid]
                cand = jnp.where(g, whole, fits)
                score = _policy_score(free, policy_id, t,
                                      jnp.asarray(n, jnp.int32), max_hosts)
                _, top = jax.lax.top_k(jnp.where(cand, score, -1), max_gang)
                take = (jnp.arange(max_gang) < n_host[jid]) & do_place
                hosts = jnp.where(take, top.astype(jnp.int32), -1)
                # the chosen hosts as a mask: vector selects, no scatter
                sel = jnp.any(hosts[:, None] == host_idx[None, :],
                              axis=0)                                # [H]
                free = free - jnp.where(sel, jnp.where(g, unit, need), 0)
                back = _release_back(sel, t, end_nom)
                gang_back = jnp.where(sel & g, back, carry[10])
            placed_plain = do_place & jnp.logical_not(g)
        else:
            host = _policy_host(free, fits, policy_id, t,
                                jnp.asarray(n, jnp.int32), max_hosts)
            free = free.at[host].add(jnp.where(do_place, -need, 0))
            sel = host_idx == host
            back = _release_back(sel, t, end_nom)
            hosts = host
            placed_plain = do_place
        # a placed plain job's release bin on its one host; a gang's went
        # to gang_back above, and an entry of 0 units banks nothing
        if fail_start is not None:
            end_bin = jnp.max(jnp.where(sel, back, 0))
        else:
            end_bin = jnp.minimum(end_nom, t_bins)
        buf_host = _put(buf_host, n, hosts)
        buf_jid = _put(buf_jid, n, jnp.where(do_place, jid, j))
        buf_end = _put(buf_end, n, end_bin)
        buf_units = _put(buf_units, n, jnp.where(placed_plain, need, 0))

        if max_backfill > 0:
            # head placed: advance past it and any backfilled successors.
            nj_adv, skip_adv = consume_skips(next_job + 1, skip >> 1)
            skip_bf = skip | jnp.where(
                place_bf,
                jnp.uint32(1) << (d_sel + 1).astype(jnp.uint32),
                jnp.uint32(0))
            next_job = jnp.where(place_head, nj_adv, next_job)
            skip = jnp.where(place_head, skip_adv, skip_bf)
            blocked = blocked | (eligible & jnp.logical_not(head_fits)
                                 & jnp.logical_not(any_bf))
        else:
            next_job = next_job + place_head.astype(jnp.int32)
            # strict FCFS: if the head job could not be placed, stop this bin.
            blocked = blocked | (eligible & jnp.logical_not(head_fits))

        return (free, next_job, skip, blocked, t,
                n + do_place.astype(jnp.int32), buf_jid, buf_host, buf_end,
                buf_units) + ((gang_back,) if gang else ())

    def _release_back(sel, t, end_nom):
        """Per host, the bin a job placed at ``t`` on the hosts ``sel``
        (one for a plain job) gives them back: its end, or under the kill
        rule (the job dies at the first outage start among its hosts that
        falls inside its run) that outage's start, and the outage host's
        own ``fail_end`` for that host.  Clipped to the horizon.  The
        ``t < fail_start`` guard keeps post-recovery placements alive (for
        them ``t >= fail_end > fail_start``)."""
        back = jnp.broadcast_to(end_nom, sel.shape)
        if fail_start is not None:
            kill = sel & fail_kill & (t < fail_start) & (end_nom > fail_start)
            kt = jnp.min(jnp.where(kill, fail_start,
                                   jnp.iinfo(jnp.int32).max))
            back = jnp.where(jnp.any(kill), jnp.where(
                kill & (fail_start == kt), fail_end, kt), back)
        return jnp.minimum(back, t_bins)

    def keep_placing(carry):
        next_job, blocked, t, n = carry[1], carry[3], carry[4], carry[5]
        return head_ready(next_job, blocked, t) & (n < max_starts_per_bin)

    def step(state, t):
        # 1) completions: the units of the slots that come back at t
        back, releases = _fire_releases(state["releases"], t)
        free = state["free"] + back
        if gang:
            free = free + jnp.where(state["gang_back"] == t, unit, 0)

        # 2) placement, bounded attempts with early exit: most bins place far
        # fewer than max_starts_per_bin jobs, and the while_loop stops as
        # soon as the head job is unsubmittable or the bin is blocked instead
        # of burning the remaining attempts on no-op iterations.
        buf_jid = jnp.full((max_starts_per_bin,), j, jnp.int32)
        buf_host = (jnp.full((max_starts_per_bin, max_gang), -1, jnp.int32)
                    if gang else jnp.zeros((max_starts_per_bin,), jnp.int32))
        buf_zero = jnp.zeros((max_starts_per_bin,), jnp.int32)
        (free, next_job, skip, blocked, _, _, buf_jid, buf_host, buf_end,
         buf_units, *gang_back) = jax.lax.while_loop(
            keep_placing, place_one,
            (free, state["next_job"], state["skip"], jnp.asarray(False), t,
             jnp.asarray(0, jnp.int32), buf_jid, buf_host, buf_zero,
             buf_zero) + ((state["gang_back"],) if gang else ()),
        )

        # 3) apply this bin's placements (unused buffer slots hold the
        # out-of-bounds sentinel job id j and are dropped by the scatter).
        job_start = state["job_start"].at[buf_jid].set(t, mode="drop")
        if gang:
            head = jnp.minimum(next_job, j - 1)
            gang_state = dict(
                job_hosts=state["job_hosts"].at[buf_jid].set(
                    buf_host, mode="drop"),
                gang_back=gang_back[0],
                gang_blocked=state["gang_blocked"]
                + (blocked & is_gang[head]).astype(jnp.int32))
            buf_host = jnp.maximum(buf_host[:, 0], 0)
        else:
            job_host = state["job_host"].at[buf_jid].set(buf_host,
                                                         mode="drop")
        releases = _bank_releases(releases, buf_host, buf_end, buf_units)

        new_state = dict(free=free, job_start=job_start, next_job=next_job,
                         skip=skip, releases=releases)
        new_state.update(gang_state if gang else dict(job_host=job_host))
        return new_state, None

    # named scopes: stable names for the device time of the placement
    # scan and the read-out in a profiler trace (HLO metadata only)
    with jax.named_scope("opendt.des_scan"):
        state, _ = jax.lax.scan(
            step, init, jnp.arange(t_bins, dtype=jnp.int32)
        )
    job_start = state["job_start"]
    job_hosts = state["job_hosts"] if gang else None
    job_host = job_hosts[:, 0] if gang else state["job_host"]

    # -- vectorized post-scan read-out ---------------------------------------
    with jax.named_scope("opendt.des_expand"):
        # Reconstructs exactly what the old per-bin accumulation produced:
        # integer counts are exact, and the float utilization scatter-adds
        # in the same job order as the per-bin segment-sum did.  Bins are
        # processed in blocks of _READOUT_BLOCK so the dense [jobs, bins]
        # intermediates stay bounded at O(jobs * block) per scenario (under
        # the scenario vmap the full-horizon version would materialize
        # [S, jobs, bins] arrays).
        started = job_start >= 0                           # [J]
        st = job_start[:, None]                            # [J, 1]
        du = dur[:, None]
        seg = jnp.where(started, job_host, max_hosts)      # sentinel bucket
        units = cores[:, None]
        if gang:
            # a gang's units are spread evenly over its hosts
            units = (cores.astype(jnp.float32)
                     / n_host.astype(jnp.float32))[:, None]
        if gang and fail_start is not None:
            # the gang kill rule: the job stops at the first outage start
            # among its hosts that falls inside its run
            on = (job_hosts >= 0) & started[:, None]               # [J, G]
            fs_g = fail_start[jnp.where(on, job_hosts, 0)]
            kill_g = (fail_kill[jnp.where(on, job_hosts, 0)] & on
                      & (st < fs_g) & (st + du > fs_g))
            end_eff = jnp.where(
                jnp.any(kill_g, axis=1, keepdims=True),
                jnp.min(jnp.where(kill_g, fs_g, jnp.iinfo(jnp.int32).max),
                        axis=1, keepdims=True),
                st + du)
        elif fail_start is not None:
            # per-job effective end: killed jobs (placed pre-outage on a
            # kill-host, overlapping its window) stop at fail_start.
            # Mirrors the kill rule of the scan's release slots above.
            h_j = jnp.where(started, job_host, 0)
            fs_j = fail_start[h_j][:, None]                # [J, 1]
            kill_j = (fail_kill[h_j] & started)[:, None]
            killed_j = kill_j & (st < fs_j) & (st + du > fs_j)
            end_eff = jnp.where(killed_j, fs_j, st + du)
        else:
            end_eff = st + du
        level_at = _phase_lookup(w.util_levels, du)

        def readout_block(tt):
            # tt [B] with -1 padding past the horizon (matches nothing below)
            running = (started[:, None] & (tt >= st)
                       & (tt < end_eff))                           # [J, B]
            u_job = level_at(tt - st)                              # [J, B]
            busy = jnp.where(
                running, u_job * units.astype(u_job.dtype), 0.0)
            host_busy = jax.ops.segment_sum(
                busy, seg, num_segments=max_hosts + 1)[:max_hosts]  # [H, B]
            if gang:
                with jax.named_scope("opendt.gang_expand"):
                    # a gang's further hosts get the same busy rows
                    for k in range(1, max_gang):
                        seg_k = jnp.where(started & (job_hosts[:, k] >= 0),
                                          job_hosts[:, k], max_hosts)
                        host_busy = host_busy + jax.ops.segment_sum(
                            busy, seg_k,
                            num_segments=max_hosts + 1)[:max_hosts]
            u_b = host_busy.T / jnp.maximum(cores_per_host, 1).astype(
                host_busy.dtype)
            started_by_t = started[:, None] & (tt >= st)           # [J, B]
            queued = jnp.sum(
                (submit[:, None] <= tt) & valid[:, None]
                & jnp.logical_not(started_by_t), axis=0).astype(jnp.int32)
            running_ct = jnp.sum(running, axis=0).astype(jnp.int32)
            return u_b, queued, running_ct

        # force_chunked_readout: a vmapping caller multiplies every
        # intermediate by its batch size, which this function cannot see —
        # the batch engine applies its own S-aware bound (see
        # scenarios.run_scenarios).
        if (not force_chunked_readout
                and j * t_bins <= _READOUT_CHUNK_THRESHOLD):
            u_th, queued, running_ct = readout_block(
                jnp.arange(t_bins, dtype=jnp.int32))
        else:
            block = min(t_bins, _READOUT_BLOCK)
            n_blocks = -(-t_bins // block)
            tt_pad = jnp.full((n_blocks * block,), -1, jnp.int32)
            tt_pad = tt_pad.at[:t_bins].set(
                jnp.arange(t_bins, dtype=jnp.int32))
            u_b, q_b, r_b = jax.lax.map(
                readout_block, tt_pad.reshape(n_blocks, block))
            u_th = u_b.reshape(n_blocks * block, max_hosts)[:t_bins]
            queued = q_b.reshape(-1)[:t_bins]
            running_ct = r_b.reshape(-1)[:t_bins]

    return SimOutput(
        u_th=u_th,
        queue_len=queued,
        running=running_ct,
        job_start=job_start,
        job_host=job_host,
        job_hosts=job_hosts,
        gang_blocked_bins=state["gang_blocked"] if gang else None,
    )


@functools.partial(jax.jit, static_argnames=("num_hosts", "cores_per_host",
                                             "t_bins", "max_starts_per_bin",
                                             "policy", "backfill_depth",
                                             "max_gang"))
def simulate_utilization(
    w: Workload,
    *,
    num_hosts: int,
    cores_per_host: "int | tuple[int, ...]",
    t_bins: int,
    max_starts_per_bin: int = 64,
    policy: "str | int | None" = None,
    backfill_depth: int = 0,
    max_gang: int = 1,
) -> SimOutput:
    """Run the vectorized DES and return the utilization field.

    Single-topology entry point: the masked core with every host active.
    ``policy``/``backfill_depth`` select the scheduler (static here — one
    compile per policy; defaults reproduce the seed worst-fit FCFS exactly).
    ``cores_per_host`` is one capacity for every host or a tuple of
    ``num_hosts`` per-host capacities (``DatacenterConfig.host_units``);
    ``max_gang > 1`` gang-schedules jobs wider than the largest host.
    See :func:`simulate_utilization_masked` for the vmap-able core and
    :mod:`repro.core.scenarios` for the batched what-if engine that sweeps
    policies and topologies in one program.
    """
    if isinstance(cores_per_host, tuple) and len(cores_per_host) != num_hosts:
        raise ValueError(f"{len(cores_per_host)} per-host capacities for "
                         f"{num_hosts} hosts")
    return simulate_utilization_masked(
        w,
        jnp.ones((num_hosts,), jnp.bool_),
        jnp.asarray(cores_per_host, jnp.int32),
        max_hosts=num_hosts,
        t_bins=t_bins,
        max_starts_per_bin=max_starts_per_bin,
        policy_id=resolve_policy(policy),
        backfill_depth=backfill_depth,
        max_backfill=int(backfill_depth),
        max_gang=max_gang,
        max_units=max(np.atleast_1d(cores_per_host)),
    )


@dataclasses.dataclass(frozen=True)
class Prediction:
    """Multi-metric prediction for a window (NFR3: >=2 perf + >=2 sust.).

    The two optional leaves are ``None`` on the default path (no carbon
    trace, no enforced cap) so legacy predictions are structurally
    unchanged; the scenario engine fills them when the corresponding
    scenario axes are in play.
    """

    power_w: Array        # [T] delivered power draw (sustainability #1)
    energy_kwh: Array     # [T] per-bin energy (sustainability #2)
    tflops: Array         # [T] achieved TFLOP/s (performance #1)
    utilization: Array    # [T] mean datacenter utilization (performance #2)
    efficiency: Array     # [T] TFLOPs per kWh (paper Fig. 5C)
    gco2: Array | None = None           # [T] per-bin carbon (sust. #3)
    power_demand_w: Array | None = None  # [T] pre-cap demand (cap analysis)
    pue: Array | None = None            # [T] dynamic PUE (facility/IT ratio)
    energy_cost: Array | None = None    # [T] per-bin cost ($, spot price)


jax.tree_util.register_pytree_node(
    Prediction,
    lambda p: ((p.power_w, p.energy_kwh, p.tflops, p.utilization,
                p.efficiency, p.gco2, p.power_demand_w, p.pue,
                p.energy_cost), None),
    lambda _, c: Prediction(*c),
)


def predict_metrics(
    u_th: Array,
    params: PowerParams,
    dc: DatacenterConfig,
    model: str = "opendc",
    carbon_intensity: Array | None = None,
    ambient_c: Array | None = None,
    price: Array | None = None,
    pue: "object | None" = None,
    backend: str = "xla",
) -> Prediction:
    """Map a utilization field to the paper's metric set (Fig. 5A/B/C).

    ``carbon_intensity`` (``[T]`` gCO2/kWh, broadcastable against the power
    trace) additionally fills the per-bin ``gco2`` leaf; without it the
    prediction is bit-for-bit the pre-carbon output with ``gco2=None``.

    ``pue`` (a :class:`repro.traces.thermal.PUEParams`) turns on the
    dynamic cooling model: the power trace becomes *facility* watts
    (IT power x PUE, with PUE a traced function of mean utilization and
    the optional ``ambient_c`` °C trace) and the per-bin PUE fills the
    ``pue`` leaf.  ``price`` (``[T]`` $/kWh) fills ``energy_cost`` from
    the (facility) energy.  All three default off, leaving the legacy
    structure untouched.

    ``backend`` selects the readout implementation: ``"xla"`` (and
    ``"auto"`` off TPU) is the unfused pipeline below, bit-for-bit the
    historical output; ``"pallas"``/``"pallas_interpret"`` route through
    the fused one-pass kernel (:mod:`repro.kernels.des_readout`), within
    oracle tolerance of the unfused path but not bitwise (padded-lane
    summation).  ``TwinConfig.kernel_backend`` threads this through
    ``twin_step``, mirroring the calibration kernel switch.
    """
    from repro.kernels.ops import resolve_backend
    from repro.traces.thermal import dynamic_pue

    if resolve_backend(backend) != "xla":
        from repro.kernels.ops import des_readout

        kw = {}
        if pue is not None:
            kw = dict(pue_base=pue.base, pue_amb_coeff=pue.amb_coeff,
                      pue_amb_ref=pue.amb_ref, pue_load_coeff=pue.load_coeff)
        rd = des_readout(
            u_th, backend=backend, p_idle=params.p_idle,
            p_max=params.p_max, r=params.r, intensity=carbon_intensity,
            ambient=ambient_c, price=price, peak_tflops=dc.peak_tflops,
            model=model, dt_seconds=SAMPLE_SECONDS, **kw)
        return Prediction(
            power_w=rd["power_w"], energy_kwh=rd["energy_kwh"],
            tflops=rd["tflops"], utilization=rd["utilization"],
            efficiency=rd["efficiency"],
            gco2=None if carbon_intensity is None else rd["gco2"],
            pue=None if pue is None else rd["pue"],
            energy_cost=None if price is None else rd["energy_cost"])

    power = datacenter_power(u_th, params, model=model)
    util = jnp.mean(u_th, axis=-1)
    pue_t = None
    if pue is not None:
        pue_t = dynamic_pue(
            util,
            None if ambient_c is None else jnp.asarray(ambient_c),
            pue)
        power = power * pue_t
    e = energy_kwh(power, SAMPLE_SECONDS)
    tflops = util * dc.peak_tflops
    eff = tflops / jnp.maximum(e, 1e-9)
    gco2 = None
    if carbon_intensity is not None:
        gco2 = carbon_gco2(e, jnp.asarray(carbon_intensity))
    cost = None
    if price is not None:
        cost = e * jnp.asarray(price, e.dtype)
    return Prediction(power_w=power, energy_kwh=e, tflops=tflops,
                      utilization=util, efficiency=eff, gco2=gco2,
                      pue=pue_t, energy_cost=cost)


def simulate(
    w: Workload,
    dc: DatacenterConfig,
    t_bins: int,
    params: PowerParams = PowerParams(),
    model: str = "opendc",
) -> tuple[SimOutput, Prediction]:
    """One-call trace-in, metrics-out simulation (FR2)."""
    if dc.host_units is not None:
        # predict_metrics' utilization mean does not weight hosts by size
        raise ValueError("simulate() runs fleets of one server size; run a "
                         "fleet of mixed sizes through run_scenarios")
    sim = simulate_utilization(
        w,
        num_hosts=dc.num_hosts,
        cores_per_host=dc.cores_per_host,
        t_bins=t_bins,
    )
    return sim, predict_metrics(sim.u_th, params, dc, model=model)
