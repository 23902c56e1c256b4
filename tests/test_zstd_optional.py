"""Regression tests: `zstandard` is optional, persistence works without it.

The seed suite died at collection on ``import zstandard`` in telemetry.
These tests pin the fix: the modules import cleanly with the package
absent, blobs round-trip under the stdlib zlib fallback, and the one-byte
codec id makes files self-describing.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import repro
from repro.core import codec

# repro is a namespace package (no __init__.py): locate src via __path__
_SRC = os.path.dirname(list(repro.__path__)[0])


def test_imports_survive_missing_zstandard():
    """`import repro.core` succeeds without zstandard.

    Runs in a subprocess with the zstandard import explicitly poisoned so the
    test holds even on machines where the package *is* installed.
    """
    snippet = (
        "import sys\n"
        "sys.modules['zstandard'] = None\n"   # poison: 'import zstandard' fails
        "import repro.core\n"
        "from repro.core import codec\n"
        "assert codec.HAVE_ZSTD is False\n"
        "assert codec.default_codec() == codec.CODEC_ZLIB\n"
        "print('IMPORT_OK')\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", snippet],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert "IMPORT_OK" in out.stdout, out.stdout + out.stderr


def test_codec_zlib_round_trip():
    data = b"windowed telemetry " * 100
    blob = codec.compress(data, codec=codec.CODEC_ZLIB)
    assert blob[:1] == codec.CODEC_ZLIB
    assert codec.decompress(blob) == data


def test_codec_rejects_unknown_id():
    with pytest.raises(ValueError):
        codec.decompress(b"\x7fgarbage")
    with pytest.raises(ValueError):
        codec.decompress(b"")


@pytest.mark.skipif(codec.HAVE_ZSTD, reason="zstandard installed")
def test_zstd_blob_without_zstandard_is_explicit():
    with pytest.raises(RuntimeError, match="zstd"):
        codec.decompress(codec.CODEC_ZSTD + b"\x28\xb5\x2f\xfdxxxx")


def test_telemetry_store_round_trip_zlib(tmp_path, monkeypatch):
    from repro.core.telemetry import TelemetryStore, clip_to_window

    # force the fallback codec regardless of the environment
    monkeypatch.setattr(codec, "HAVE_ZSTD", False)

    rng = np.random.default_rng(0)
    store = TelemetryStore(bins_per_window=6)
    for win in range(3):
        tw = clip_to_window(
            win, 6, win * 6,
            rng.random((6, 4)).astype(np.float32),
            rng.uniform(1e3, 2e3, 6),
            temp=rng.random(6).astype(np.float32),
        )
        store.ingest(tw)
    path = str(tmp_path / "telemetry.bin")
    store.flush(path)
    with open(path, "rb") as f:
        assert f.read(1) == codec.CODEC_ZLIB

    loaded = TelemetryStore.load(path)
    assert sorted(loaded.windows()) == [0, 1, 2]
    for win in range(3):
        a, b = store.get(win), loaded.get(win)
        np.testing.assert_array_equal(a.u_th, b.u_th)
        np.testing.assert_array_equal(a.power_w, b.power_w)
        np.testing.assert_array_equal(a.extras["temp"], b.extras["temp"])
