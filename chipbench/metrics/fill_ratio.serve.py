"""Mean share (%) of the service's lanes active per dispatched batch
(``ServeStats.fill_ratio``)."""


def read(run):
    v = run.counters.get("fill_ratio")
    return None if v is None else v * 100.0
