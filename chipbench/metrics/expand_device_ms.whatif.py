"""Device self time (ms) of the DES read-out (the dense utilization expand)
per batch: the ``opendt.des_expand`` scope, from the trace."""

from chipbench.spans import scope_ms


def read(run):
    return scope_ms(run, "opendt.des_expand")
