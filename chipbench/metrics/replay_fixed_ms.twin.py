"""Host time (ms) per replay outside its windows: the ``opendt.twin.init``,
``opendt.des`` and ``opendt.twin.finish`` spans."""

from chipbench.spans import span_ms

SPANS = ("opendt.twin.init", "opendt.des", "opendt.twin.finish")


def read(run):
    return span_ms(run, SPANS, per="request")
