"""Record a small trace for ``test_chipbench_trace.py`` to reduce.

    python3 chipbench/tests/record_trace.py <out.xplane.pb>

Three requests of a small jitted program, each inside a ``bench.request``
annotation with a 2 ms ``bench.pause`` on the host after it, traced with
the same profiler options as the harness.  ``data/tiny-cpu.xplane.pb`` was
recorded with ``JAX_PLATFORMS=cpu``.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(dest: str) -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(d, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.request"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.pause"):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, dest)
    shutil.rmtree(d)
    print(dest, os.path.getsize(dest))


if __name__ == "__main__":
    main(sys.argv[1])
