"""Device self time (ms) of the DES placement scan per batch: the
``opendt.des_scan`` scope, from the trace."""

from chipbench.spans import scope_ms


def read(run):
    return scope_ms(run, "opendt.des_scan")
