"""Share (%) of the traced window in which the device ran no operation."""

from chipbench.readers import device_idle


def read(run):
    return device_idle(run)
