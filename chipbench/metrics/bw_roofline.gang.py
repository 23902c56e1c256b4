"""Least time of a gang what-if batch's bytes at peak bandwidth, as a
share (%) of its device time."""

from chipbench.readers import bw_roofline

PROGRAMS = ("_run_scenarios_body",)


def read(run):
    return bw_roofline(run, *PROGRAMS)
