"""Device self time (ms) of the power read-out per batch: the
``opendt.power_readout`` scope, from the trace."""

from chipbench.spans import scope_ms


def read(run):
    return scope_ms(run, "opendt.power_readout")
