"""Device time (ms) of the jitted step per one-week replay, from the trace."""

from chipbench.readers import step_device_ms

PROGRAMS = ("simulate_utilization", "twin_step",)


def read(run):
    return step_device_ms(run, *PROGRAMS)
