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


def _policy_host(free: Array, fits: Array, policy_id: Array,
                 t: Array, salt: Array, max_hosts: int) -> Array:
    """Branchless host selection: argmax of a policy-indexed score.

    Builds the [4, max_hosts] score table (all int32, all >= 0 so the -1
    "does not fit" sentinel always loses), gathers the row for the *traced*
    ``policy_id``, and takes the argmax over fitting hosts.  Ties break to
    the lowest host index (argmax returns the first maximum), which makes
    WORST_FIT bit-identical to the pre-policy-kernel scheduler
    ``argmax(where(fits, free, -1))``.
    """
    idx = jnp.arange(max_hosts, dtype=jnp.int32)
    scores = jnp.stack([
        max_hosts - idx,                                    # FIRST_FIT
        _BEST_FIT_BIAS - jnp.minimum(free, _BEST_FIT_BIAS - 1),  # BEST_FIT
        free,                                               # WORST_FIT
        _hash_scores(idx, t, salt),                         # RANDOM_FIT
    ])
    score = scores[jnp.clip(policy_id, 0, len(PLACEMENT_POLICIES) - 1)]
    return jnp.argmax(jnp.where(fits, score, -1))


@dataclasses.dataclass(frozen=True)
class SimOutput:
    """Dense simulation read-out at 5-minute granularity.

    Attributes:
      u_th: ``[T, H]`` per-host utilization in [0, 1].
      queue_len: ``[T]`` jobs submitted but not yet started.
      running: ``[T]`` jobs running.
      job_start: ``[J]`` assigned start bin (-1 if never started).
      job_host: ``[J]`` assigned host (-1 if never started).
    """

    u_th: Array
    queue_len: Array
    running: Array
    job_start: Array
    job_host: Array


jax.tree_util.register_pytree_node(
    SimOutput,
    lambda s: ((s.u_th, s.queue_len, s.running, s.job_start, s.job_host), None),
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
) -> SimOutput:
    """Masked-host-axis DES core (trace-level; callers jit/vmap it).

    The host axis is padded to a static ``max_hosts``; ``host_mask [max_hosts]``
    marks the active hosts and ``cores_per_host`` is a *traced* int32 scalar.
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

    Placement (the event-driven part) is a bounded policy-kernel loop inside
    the scan body; utilization accumulation is a segment-sum scatter over
    host assignments.  Utilization is *independent of power-model
    parameters* — the structural fact the Self-Calibrator exploits (see
    calibrate.py).
    """
    if not 0 <= max_backfill <= 31:
        # the skip bitmask is uint32 and bit max_backfill must be addressable
        raise ValueError(f"max_backfill must be in [0, 31], got {max_backfill}")
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

    # The scan carries *placement state only*: which job starts where/when,
    # free cores, a [t_bins+1, max_hosts] core-release table written at
    # placement time (row t_bins absorbs clipped past-horizon releases), and
    # a skip bitmask of backfilled jobs ahead of the FCFS pointer.
    # Everything read out per bin (utilization field, queue depth, running
    # count) is reconstructed vectorized AFTER the scan from job_start —
    # per-bin O(jobs) passes inside the scan would dominate the runtime and,
    # under the scenario vmap, multiply by S with no amortization.
    init = dict(
        free=jnp.where(host_mask, cores_per_host, 0).astype(jnp.int32),
        job_host=jnp.full((j,), -1, jnp.int32),
        job_start=jnp.full((j,), -1, jnp.int32),
        next_job=jnp.asarray(0, jnp.int32),
        # bit d set <=> job next_job+d already started via backfill.  Bit 0 is
        # never set at rest: every pointer advance immediately consumes the
        # trailing run of set bits, so the head is always an unstarted job.
        skip=jnp.asarray(0, jnp.uint32),
        release=jnp.zeros((t_bins + 1, max_hosts), jnp.int32),
    )

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
    # attempt.  Instead each attempt records (job, host) into a
    # [max_starts_per_bin] buffer; the buffers are scattered into the scan
    # carry once per bin.  Every iteration either places exactly one job or
    # sets `blocked` (ending the bin), so the loop is bounded by
    # max_starts_per_bin placements.
    def place_one(carry):
        free, next_job, skip, blocked, t, n, buf_jid, buf_host = carry
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
            startable = elig_c & jnp.any(fits_c, axis=1)             # [K]
            any_bf = jnp.any(startable)
            d_sel = jnp.argmax(startable)        # first startable offset - 1
            place_bf = eligible & jnp.logical_not(head_fits) & any_bf
            jid = jnp.where(place_head, jid_h, jid_c[d_sel])
        else:
            place_bf = jnp.asarray(False)
            jid = jid_h

        need = cores[jid]
        fits = (free >= need) & online
        host = _policy_host(free, fits, policy_id, t,
                            jnp.asarray(n, jnp.int32), max_hosts)
        do_place = place_head | place_bf
        free = free.at[host].add(jnp.where(do_place, -need, 0))
        buf_jid = buf_jid.at[n].set(jnp.where(do_place, jid, j))
        buf_host = buf_host.at[n].set(host)

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
                n + do_place.astype(jnp.int32), buf_jid, buf_host)

    def keep_placing(carry):
        free, next_job, skip, blocked, t, n, buf_jid, buf_host = carry
        return head_ready(next_job, blocked, t) & (n < max_starts_per_bin)

    def step(state, t):
        # 1) completions: cores banked in the release table at placement time.
        free = state["free"] + state["release"][t]

        # 2) placement, bounded attempts with early exit: most bins place far
        # fewer than max_starts_per_bin jobs, and the while_loop stops as
        # soon as the head job is unsubmittable or the bin is blocked instead
        # of burning the remaining attempts on no-op iterations.
        buf_jid = jnp.full((max_starts_per_bin,), j, jnp.int32)
        buf_host = jnp.zeros((max_starts_per_bin,), jnp.int32)
        free, next_job, skip, _, _, _, buf_jid, buf_host = jax.lax.while_loop(
            keep_placing, place_one,
            (free, state["next_job"], state["skip"], jnp.asarray(False), t,
             jnp.asarray(0, jnp.int32), buf_jid, buf_host),
        )

        # 3) apply this bin's placements (unused buffer slots hold the
        # out-of-bounds sentinel job id j and are dropped by the scatter).
        jj = jnp.minimum(buf_jid, j - 1)
        placed = buf_jid < j
        job_host = state["job_host"].at[buf_jid].set(buf_host, mode="drop")
        job_start = state["job_start"].at[buf_jid].set(t, mode="drop")
        end_nom = t + dur[jj]
        if fail_start is not None:
            # kill rule, applied at placement time: a job landing on a
            # kill-host *before* its outage and running into it dies at
            # fail_start, and its cores come back with the host at
            # fail_end.  The `t < fail_start` guard keeps post-recovery
            # placements alive (for them t >= fail_end > fail_start).
            killed = (fail_kill[buf_host] & (t < fail_start[buf_host])
                      & (end_nom > fail_start[buf_host]))
            end_bin = jnp.minimum(
                jnp.where(killed, fail_end[buf_host], end_nom), t_bins)
        else:
            end_bin = jnp.minimum(end_nom, t_bins)
        release = state["release"].at[end_bin, buf_host].add(
            jnp.where(placed, cores[jj], 0))

        new_state = dict(free=free, job_host=job_host, job_start=job_start,
                         next_job=next_job, skip=skip, release=release)
        return new_state, None

    # named scopes: stable names for the device time of the placement
    # scan and the read-out in a profiler trace (HLO metadata only)
    with jax.named_scope("opendt.des_scan"):
        state, _ = jax.lax.scan(
            step, init, jnp.arange(t_bins, dtype=jnp.int32)
        )
    job_start, job_host = state["job_start"], state["job_host"]

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
        if fail_start is not None:
            # per-job effective end: killed jobs (placed pre-outage on a
            # kill-host, overlapping its window) stop at fail_start.
            # Mirrors the release-table kill rule above.
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
                running, u_job * cores[:, None].astype(u_job.dtype), 0.0)
            host_busy = jax.ops.segment_sum(
                busy, seg, num_segments=max_hosts + 1)[:max_hosts]  # [H, B]
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
    )


@functools.partial(jax.jit, static_argnames=("num_hosts", "cores_per_host",
                                             "t_bins", "max_starts_per_bin",
                                             "policy", "backfill_depth"))
def simulate_utilization(
    w: Workload,
    *,
    num_hosts: int,
    cores_per_host: int,
    t_bins: int,
    max_starts_per_bin: int = 64,
    policy: "str | int | None" = None,
    backfill_depth: int = 0,
) -> SimOutput:
    """Run the vectorized DES and return the utilization field.

    Single-topology entry point: the masked core with every host active.
    ``policy``/``backfill_depth`` select the scheduler (static here — one
    compile per policy; defaults reproduce the seed worst-fit FCFS exactly).
    See :func:`simulate_utilization_masked` for the vmap-able core and
    :mod:`repro.core.scenarios` for the batched what-if engine that sweeps
    policies and topologies in one program.
    """
    return simulate_utilization_masked(
        w,
        jnp.ones((num_hosts,), jnp.bool_),
        cores_per_host,
        max_hosts=num_hosts,
        t_bins=t_bins,
        max_starts_per_bin=max_starts_per_bin,
        policy_id=resolve_policy(policy),
        backfill_depth=backfill_depth,
        max_backfill=int(backfill_depth),
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
    sim = simulate_utilization(
        w,
        num_hosts=dc.num_hosts,
        cores_per_host=dc.cores_per_host,
        t_bins=t_bins,
    )
    return sim, predict_metrics(sim.u_th, params, dc, model=model)
