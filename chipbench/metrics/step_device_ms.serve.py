"""Device time (ms) of the jitted step per dispatched batch, from the trace."""

from chipbench.readers import step_device_ms

PROGRAMS = ("_fleet_step_masked",)


def read(run):
    return step_device_ms(run, *PROGRAMS)
