"""Host time (ms) per twinned window waiting for the step's prediction:
the ``opendt.window.wait`` span."""

from chipbench.spans import span_ms


def read(run):
    return span_ms(run, ("opendt.window.wait",))
