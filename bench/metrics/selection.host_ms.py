"""Host time per round in the selection layer (``FLEngine._select_lanes``:
counter mask, Eq. 3 windows, CSMA contention), from the benchmark's
``fl.select`` spans. Device contention runs inside this span."""


def read(v):
    ns = v.trace.span_ns("fl.select")
    return ns / v.rounds * 1e-6 if ns else None
