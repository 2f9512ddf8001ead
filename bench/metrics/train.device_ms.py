"""Device time per round of the jitted local-training program (Eq. 2
fused in): ``sweep_round`` on fused rounds, ``round_fn`` on winner-sparse
ones, averaged over the chips."""


def read(v):
    ns = v.trace.module_ns(r"^jit_(sweep_round|round_fn)$")
    return ns / v.rounds * 1e-6 if ns else None
