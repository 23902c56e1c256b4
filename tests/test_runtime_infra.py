"""Fault tolerance, stragglers, elastic re-mesh, sharding rules, MoE, HLO
analysis (trip-count multiplication in a subprocess with 8 host devices)."""

import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.runtime.elastic import plan_mesh
from repro.runtime.fault import (
    FailureInjector,
    FaultConfig,
    run_with_restarts,
)
from repro.runtime.straggler import StragglerConfig, StragglerDetector


def test_run_with_restarts_resumes(tmp_path):
    calls = []

    def make_state():
        return {"x": np.zeros((1,), np.float32)}

    def step_fn(state, step):
        calls.append(step)
        return {"x": state["x"] + 1}, float(state["x"][0])

    rep = run_with_restarts(
        total_steps=20,
        make_state=make_state,
        step_fn=step_fn,
        fault_cfg=FaultConfig(ckpt_dir=str(tmp_path), ckpt_every=5),
        injector=FailureInjector(fail_at_steps=(7, 13)),
    )
    assert rep.steps_done == 20
    assert rep.restarts == 2
    assert rep.restored_from == [5, 10]
    # state continuity: steps 5 and 10 re-executed after the crashes;
    # the failing step itself never ran before the crash (check precedes it)
    assert calls.count(5) == 2 and calls.count(10) == 2
    assert calls.count(13) == 1


def test_checkpoint_gc_keeps_latest(tmp_path):
    for s in range(6):
        ckpt.save(str(tmp_path), s, {"v": np.array([s])}, keep=2)
    assert ckpt.latest_step(str(tmp_path)) == 5
    step, state = ckpt.restore(str(tmp_path))
    assert state["v"][0] == 5


def test_straggler_detector_flags_slow_host():
    det = StragglerDetector(8, StragglerConfig(min_samples=2, hysteresis=2))
    base = np.ones(8)
    props = []
    for w in range(6):
        t = base.copy()
        t[3] = 2.0                       # host 3 persistently 2x slower
        props += det.observe(t, w)
    assert props, "straggler never flagged"
    assert props[0].impact["host"] == 3
    assert props[0].impact["ratio"] > 1.5


def test_elastic_plan_mesh():
    plan = plan_mesh(512, model_parallel=16, global_batch=256, prefer_pods=2)
    assert plan.shape == (2, 16, 16)
    # lose 32 devices -> data shrinks, global batch preserved
    plan2 = plan_mesh(480, model_parallel=16, global_batch=256)
    assert plan2.data_shards * plan2.per_shard_batch == 256
    assert plan2.shape[-1] == 16
    with pytest.raises(RuntimeError):
        plan_mesh(8, model_parallel=16, global_batch=256)


def test_sharding_rules():
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import logical_to_spec

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2)
    # trivial mesh: everything replicated
    assert logical_to_spec(("batch", "embed"), (8, 16), mesh, "train") == P()

    # fake bigger mesh via abstract mesh
    mesh = jax.sharding.AbstractMesh((4, 2), ("data", "model"))
    spec = logical_to_spec(("batch", "ff"), (8, 16), mesh, "train")
    assert spec == P(("data",), "model") or spec == P("data", "model")
    # non-divisible dims drop their sharding
    spec = logical_to_spec(("batch", "ff"), (6, 16), mesh, "train")
    assert spec == P(None, "model")
    # an axis is consumed at most once
    spec = logical_to_spec(("ff", "vocab"), (16, 32), mesh, "train")
    assert spec == P("model")


def test_compile_cache_directory(monkeypatch, tmp_path):
    """The chip entry points' cache: JAX_COMPILATION_CACHE_DIR wins and is
    left to JAX; unset, the cache goes to the fixed <repo>/.jax_cache."""
    import pathlib

    import jax

    from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
        assert REPO_CACHE_DIR == (
            pathlib.Path(__file__).resolve().parents[1] / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_moe_capacity_and_gates():
    from repro.configs.base import ModelConfig
    from repro.models.moe import _capacity, _moe_local

    cfg = ModelConfig(name="t", family="moe", num_layers=1, d_model=16,
                      vocab=8, moe=True, n_experts=4, top_k=2, moe_d_ff=8,
                      capacity_factor=8.0).validate()
    rng = np.random.default_rng(0)
    tl = 32
    x = jnp.asarray(rng.normal(0, 1, (tl, 16)).astype(np.float32))
    router = jnp.asarray(rng.normal(0, 1, (16, 4)).astype(np.float32))
    wg = jnp.asarray(rng.normal(0, .1, (16, 16, 8)).astype(np.float32))
    wu = jnp.asarray(rng.normal(0, .1, (16, 16, 8)).astype(np.float32))
    wd = jnp.asarray(rng.normal(0, .1, (16, 8, 16)).astype(np.float32))
    y, aux = _moe_local(x, router, wg, wu, wd, cfg=cfg, e0=0, n_shards=1)
    assert y.shape == x.shape and np.isfinite(np.asarray(y)).all()
    assert float(aux) > 0

    # ample capacity: output must equal the dense gather-all-experts form
    probs = np.asarray(jnp.asarray(
        __import__("jax").nn.softmax(x @ router, axis=-1)))
    idx = np.argsort(-probs, axis=1)[:, :2]
    want = np.zeros_like(np.asarray(x))
    for t in range(tl):
        for e in idx[t]:
            h = np.asarray(x)[t] @ np.asarray(wg)[e]
            h = h / (1 + np.exp(-h)) * (np.asarray(x)[t] @ np.asarray(wu)[e])
            want[t] += probs[t, e] * (h @ np.asarray(wd)[e])
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-3, atol=2e-3)


HLO_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # the stripped subprocess env must not let jax probe absent accelerators
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.analysis.hlo import analyze_compiled_text
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2)
    L, B, D, F = 6, 8, 64, 128

    def step(ws, x):
        def body(c, w):
            return jnp.tanh(c @ w), ()
        y, _ = jax.lax.scan(body, x, ws)
        return y.sum()

    ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    x = jax.ShapeDtypeStruct((B, D), jnp.float32)
    j = jax.jit(step,
                in_shardings=(NamedSharding(mesh, P(None, None, "model")),
                              NamedSharding(mesh, P("data", None))),
                out_shardings=NamedSharding(mesh, P()))
    compiled = j.lower(ws, x).compile()
    parsed = analyze_compiled_text(compiled.as_text(), 8)
    expect = L * 2 * (B // 2) * D * (D // 4)   # per-device dot flops x L trips
    ratio = parsed["flops_per_device"] / expect
    assert 0.9 < ratio < 1.6, (parsed["flops_per_device"], expect)
    print("OK", parsed["flops_per_device"], expect)
""")


def test_hlo_triptcount_multiplication_subprocess():
    out = subprocess.run(
        [sys.executable, "-c", HLO_SUBPROC],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root"},
        cwd="/root/repo",
    )
    assert "OK" in out.stdout, out.stdout + out.stderr
