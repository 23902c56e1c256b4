"""Device self time (ms) of the calibration per twinned window: the
``opendt.twin_calibrate`` scope of ``twin_step``, from the trace."""

from chipbench.spans import scope_ms


def read(run):
    return scope_ms(run, "opendt.twin_calibrate", per="window")
