"""Least time of a dispatched batch's bytes at peak bandwidth, as a share
(%) of its device time."""

from chipbench.readers import bw_roofline

PROGRAMS = ("_fleet_step_masked",)


def read(run):
    return bw_roofline(run, *PROGRAMS)
