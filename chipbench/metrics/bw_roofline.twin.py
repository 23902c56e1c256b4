"""Least time of a one-week replay's bytes at peak bandwidth, as a share
(%) of its device time."""

from chipbench.readers import bw_roofline

PROGRAMS = ("simulate_utilization", "twin_step",)


def read(run):
    return bw_roofline(run, *PROGRAMS)
