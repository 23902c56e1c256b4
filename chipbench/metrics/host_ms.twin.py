"""Host time (ms) per twinned window: the gap between consecutive
``WindowRecord.started_at`` less the fused step's ``sim_seconds``."""

from chipbench.readers import mean_ms


def read(run):
    return mean_ms(run.program_spans.get("window_host_s"))
