"""Host time (ms) per twinned window to call the jitted step: the
``opendt.window.step`` span."""

from chipbench.spans import span_ms


def read(run):
    return span_ms(run, ("opendt.window.step",))
