"""Device time (ms) of the jitted step per gang what-if batch, from the
trace."""

from chipbench.readers import step_device_ms

PROGRAMS = ("_run_scenarios_body",)


def read(run):
    return step_device_ms(run, *PROGRAMS)
