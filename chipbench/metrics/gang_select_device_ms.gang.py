"""Device self time (ms) of the gang host choice inside the placement scan
per batch: the ``opendt.gang_select`` scope, from the trace."""

from chipbench.spans import scope_ms


def read(run):
    return scope_ms(run, "opendt.gang_select")
