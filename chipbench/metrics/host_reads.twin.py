"""Device-to-host reads per twinned window: the mean of
``WindowRecord.host_reads`` over the windows of the replays."""


def read(run):
    return run.counters.get("host_reads")
