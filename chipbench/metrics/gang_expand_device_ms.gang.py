"""Device self time (ms) of the gang part of the read-out (a gang's further
hosts) per batch: the ``opendt.gang_expand`` scope, from the trace."""

from chipbench.spans import scope_ms


def read(run):
    return scope_ms(run, "opendt.gang_expand")
