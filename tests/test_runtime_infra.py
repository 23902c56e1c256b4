"""Runtime infrastructure: straggler detection and the compile cache."""

import numpy as np

from repro.runtime.straggler import StragglerConfig, StragglerDetector


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
