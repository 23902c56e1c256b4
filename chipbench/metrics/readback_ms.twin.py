"""Host time (ms) per twinned window to read results back and keep the
records: the ``opendt.window.readback`` span."""

from chipbench.spans import span_ms


def read(run):
    return span_ms(run, ("opendt.window.readback",))
