"""Record a small trace of the twin for ``test_chipbench_spans.py``.

    python3 chipbench/tests/record_tpu_trace.py <out.xplane.pb>

A 16-host, two-window ``DigitalTwin`` replay (its DES and two fused steps),
run once before the profiler starts so that nothing compiles in the trace,
then traced with the harness's profiler options.  Beside the trace it
writes ``<out>.scopes.json``, the scopes of the compiled programs'
instructions (:func:`chipbench.spans.compiled_scopes`), which the reduction
needs to place a TPU trace's operations in their scopes.
``data/tiny-tpu.xplane.pb`` was recorded on one TPU v5e chip.
"""

import glob
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(dest: str) -> None:
    import jax

    import repro.core  # noqa: F401  (the package fixes the import order)
    from chipbench import spans
    from repro.core.orchestrator import OrchestratorConfig
    from repro.core.twin import DigitalTwin, TraceGroundTruth
    from repro.traces.schema import DatacenterConfig
    from repro.traces.surf import SurfTraceSpec, make_surf22_like

    jax.config.update("jax_enable_compilation_cache", False)
    dc = DatacenterConfig(num_hosts=16, cores_per_host=16)
    t_bins = 72
    w = make_surf22_like(SurfTraceSpec(days=0.25, seed=3), dc)
    truth = TraceGroundTruth(w, dc, t_bins)

    def replay():
        DigitalTwin(w, dc, t_bins,
                    OrchestratorConfig(bins_per_window=36)).run(truth.window)

    with spans.compiled_scopes() as scopes:
        replay()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(d, profiler_options=opts)
    replay()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, dest)
    shutil.rmtree(d)
    with open(dest + ".scopes.json", "w") as f:
        json.dump({k: v for k, v in scopes.items() if v}, f, indent=0,
                  sort_keys=True)
    print(dest, os.path.getsize(dest))
    print(spans.reduce_file(dest, scopes))


if __name__ == "__main__":
    main(sys.argv[1])
