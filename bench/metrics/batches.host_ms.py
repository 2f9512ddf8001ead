"""Host time per round in the batch pipeline (``HostBackend.sweep_batches``:
epoch permutations and the gather of every user's batches), from the
benchmark's ``fl.batches`` spans."""


def read(v):
    ns = v.trace.span_ns("fl.batches")
    return ns / v.rounds * 1e-6 if ns else None
