"""Host time (ms) per what-if batch: ``build_scenario_set`` plus
``summarize_scenarios``."""

from chipbench.readers import mean_ms


def read(run):
    return mean_ms(run.spans.get("host_whatif"))
