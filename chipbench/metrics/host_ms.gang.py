"""Host time (ms) per what-if batch on the gang cluster:
``build_scenario_set`` plus ``summarize_scenarios``."""

from chipbench.readers import mean_ms


def read(run):
    return mean_ms(run.spans.get("host_gang"))
