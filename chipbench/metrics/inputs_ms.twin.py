"""Host time (ms) per twinned window to take in telemetry and build the
step's inputs: the ``opendt.ingest`` and ``opendt.window.inputs`` spans."""

from chipbench.spans import span_ms


def read(run):
    return span_ms(run, ("opendt.ingest", "opendt.window.inputs"))
