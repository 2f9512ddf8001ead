"""Device time per round of the jitted Eq. 1 merge program
(``sweep_merge``), averaged over the chips: the Mosaic gather kernel on
one chip; on a mesh that splits the users, the partitioned merge with
its cross-chip reduction."""


def read(v):
    ns = v.trace.module_ns(r"^jit_sweep_merge$")
    return ns / v.rounds * 1e-6 if ns else None
