"""Fleet-axis sharding: shard_map over D == single-device vmap, bit for bit.

Mirrors ``test_shard_scenarios.py`` for the *fleet* axis (ROADMAP item 5):
``run_fleet(shard=True)`` and ``fleet_step_masked(shard=True)`` spread twin
lanes across the device mesh with padded replica lanes and must reproduce
the vmap path bit for bit.  Runs meaningfully at any device count: with one
device the mesh is trivial (the path is still exercised end to end); the
``tier1-multidevice`` CI job re-runs this module under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so the real
multi-device path — including D-axis padding when D is not a multiple of
the device count — is covered on CPU-only CI.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.state import (
    SimSlice,
    TelemetrySlice,
    TwinConfig,
    init_twin_state,
    make_telemetry,
    twin_step,
)
from repro.core.twin import (
    FLEET_AXIS,
    fleet_mesh,
    fleet_step_masked,
    index_twin_state,
    run_fleet,
    stack_twin_states,
)
from repro.traces.schema import DatacenterConfig

DC = DatacenterConfig(num_hosts=8, cores_per_host=4)
CFG = TwinConfig(bins_per_window=12, dc=DC)

_solo_step = jax.jit(twin_step)  # non-donating solo reference


def _telem(seed: int):
    r = np.random.default_rng(seed)
    u = r.uniform(0, 1, (12, 8)).astype(np.float32)
    p = (8 * 70 + 2240 * r.uniform(0.2, 0.9, 12)).astype(np.float32)
    return u, p


def _fleet_inputs(n_windows: int, n_dc: int):
    """``run_fleet`` inputs, leaves ``[W, D, ...]`` (lane d, window w keyed
    by seed ``100 * d + w`` so every lane is an independent stream)."""
    us = np.stack([[_telem(100 * d + w)[0] for d in range(n_dc)]
                   for w in range(n_windows)])
    ps = np.stack([[_telem(100 * d + w)[1] for d in range(n_dc)]
                   for w in range(n_windows)])
    telem = TelemetrySlice(u_th=jnp.asarray(us), power_w=jnp.asarray(ps),
                           valid=jnp.ones((n_windows, n_dc), bool))
    return telem, SimSlice(u_th=jnp.asarray(us))


def _step_inputs(n_dc: int, seed0: int = 0):
    """``fleet_step_masked`` inputs, leaves ``[D, ...]`` (one window)."""
    us = np.stack([_telem(seed0 + d)[0] for d in range(n_dc)])
    ps = np.stack([_telem(seed0 + d)[1] for d in range(n_dc)])
    telem = TelemetrySlice(u_th=jnp.asarray(us), power_w=jnp.asarray(ps),
                           valid=jnp.ones((n_dc,), bool))
    return telem, SimSlice(u_th=jnp.asarray(us))


def _fresh_fleet(d: int):
    return stack_twin_states([init_twin_state(CFG) for _ in range(d)])


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_run_fleet_sharded_matches_vmap_bitwise():
    """The acceptance gate: shard_map over the D axis reproduces the
    single-device vmap path bit for bit — final states and every window's
    outputs.  D=6 on purpose: not a multiple of 2 or 4 devices, so the
    multi-device CI leg exercises replica-lane padding."""
    d, w = 6, 3
    telem, sims = _fleet_inputs(w, d)
    ref_final, ref_outs = run_fleet(_fresh_fleet(d), telem, sims)
    sh_final, sh_outs = run_fleet(_fresh_fleet(d), telem, sims, shard=True)
    _assert_trees_equal(ref_final, sh_final)
    _assert_trees_equal(ref_outs, sh_outs)


def test_run_fleet_sharded_matches_solo_lanes():
    """Transitively with the vmap gate: every sharded lane is exactly the
    solo ``twin_step`` stream (the solo == lane == sharded-lane invariant)."""
    d, w = 3, 2
    telem, sims = _fleet_inputs(w, d)
    final, outs = run_fleet(_fresh_fleet(d), telem, sims, shard=True)
    for dc_i in range(d):
        st = init_twin_state(CFG)
        for w_i in range(w):
            u, p = _telem(100 * dc_i + w_i)
            st, out = _solo_step(st, make_telemetry(u, p),
                                 SimSlice(u_th=jnp.asarray(u)))
            np.testing.assert_array_equal(
                np.asarray(outs.mape)[w_i, dc_i], np.asarray(out.mape))
        _assert_trees_equal(st, index_twin_state(final, dc_i))


def test_fleet_step_masked_sharded_matches_vmap_bitwise():
    """The serve-path step: masked lanes (mixed fill) through the sharded
    program match the vmap path bit for bit, inactive lanes included."""
    d = 5
    telem, sims = _step_inputs(d)
    active = jnp.asarray([True, False, True, True, False])
    ref_fleet, ref_outs = fleet_step_masked(_fresh_fleet(d), telem, sims,
                                            active)
    sh_fleet, sh_outs = fleet_step_masked(_fresh_fleet(d), telem, sims,
                                          active, shard=True)
    _assert_trees_equal(ref_fleet, sh_fleet)
    _assert_trees_equal(ref_outs, sh_outs)


def test_explicit_mesh_and_padding():
    """D not divisible by the device count: lanes pad with lane-0 replicas
    and both outputs slice back to the true D."""
    n_dev = len(jax.devices())
    mesh = fleet_mesh(n_dev)
    assert mesh.shape[FLEET_AXIS] == n_dev
    d, w = 5, 2                          # D=5: pads for any n_dev > 1
    telem, sims = _fleet_inputs(w, d)
    final, outs = run_fleet(_fresh_fleet(d), telem, sims, shard=True,
                            mesh=mesh)
    assert np.asarray(outs.mape).shape == (w, d)
    assert jax.tree.leaves(final)[0].shape[0] == d
    ref_final, ref_outs = run_fleet(_fresh_fleet(d), telem, sims)
    _assert_trees_equal(ref_final, final)
    _assert_trees_equal(ref_outs, outs)


def test_one_lane_per_device():
    """D == device count: the engine pads to >= 2 lanes per device (a
    batch-1 vmap is not bitwise on TPU) and must still match the vmap
    path bit for bit."""
    d = len(jax.devices())
    telem, sims = _step_inputs(d, seed0=40)
    active = jnp.ones((d,), bool)
    ref = fleet_step_masked(_fresh_fleet(d), telem, sims, active)
    sh = fleet_step_masked(_fresh_fleet(d), telem, sims, active, shard=True)
    _assert_trees_equal(ref, sh)


def test_multidevice_actually_shards():
    """Under the forced multi-device CI environment the outputs must really
    be computed across >1 device (not silently replicated)."""
    if len(jax.devices()) < 2:
        pytest.skip("single-device environment (multi-device CI covers this)")
    d, w = 4, 2
    telem, sims = _fleet_inputs(w, d)
    final, outs = run_fleet(_fresh_fleet(d), telem, sims, shard=True)
    assert np.asarray(outs.mape).shape == (w, d)
    assert np.isfinite(np.asarray(outs.mape)).all()


def test_sharded_single_compilation():
    """ONE compile per path: a warm re-run with fresh values must not grow
    either jit cache (the `_cache_size` acceptance gate from the ISSUE)."""
    if run_fleet._cache_size is None or fleet_step_masked._cache_size is None:
        pytest.skip("jax private _cache_size API unavailable")
    d, w = 4, 2
    telem, sims = _fleet_inputs(w, d)
    final, _ = run_fleet(_fresh_fleet(d), telem, sims, shard=True)
    after_first = run_fleet._cache_size()
    run_fleet(final, telem, sims, shard=True)
    assert run_fleet._cache_size() == after_first

    stelem, ssims = _step_inputs(d)
    active = jnp.ones((d,), bool)
    sfleet, _ = fleet_step_masked(_fresh_fleet(d), stelem, ssims, active,
                                  shard=True)
    after_step = fleet_step_masked._cache_size()
    fleet_step_masked(sfleet, stelem, ssims, active, shard=True)
    assert fleet_step_masked._cache_size() == after_step


def test_serve_sharded_matches_unsharded():
    """`TwinService(shard=True)` spreads resident tenants across devices and
    must serve the identical result stream (the dispatch path is the same
    `fleet_step_masked` this module pins against vmap)."""
    from repro.serve import ServeConfig, SyntheticProducer, TwinService

    dc = DatacenterConfig(num_hosts=4, cores_per_host=4)
    twin = TwinConfig(bins_per_window=6, dc=dc)

    def run(shard: bool):
        svc = TwinService(ServeConfig(twin=twin, lanes=4, queue_capacity=64,
                                      shard=shard))
        events = []
        for i, t in enumerate(["a", "b", "c"]):
            svc.admit(t)
            p = SyntheticProducer(t, hosts=dc.num_hosts,
                                  bins_per_window=twin.bins_per_window,
                                  num_windows=2, seed=i)
            events.extend(p.poll(float("inf")))
        for ev in sorted(events, key=lambda e: (e.window, e.tenant)):
            assert svc.submit(ev)
        svc.run_until_idle(pump=False)
        return {(r.tenant, r.window): jax.tree.map(np.asarray, r.output)
                for r in svc.drain()}

    ref, sh = run(False), run(True)
    assert ref.keys() == sh.keys() and len(ref) == 6
    for k in ref:
        _assert_trees_equal(ref[k], sh[k])


def test_mesh_requires_shard_flag():
    from repro.serve import ServeConfig

    with pytest.raises(ValueError, match="mesh given but shard=False"):
        ServeConfig(twin=CFG, lanes=2, mesh=fleet_mesh(1))
