"""Roofline share of the Eq. 1 merge kernel (``kernels/gather.py``):
the least time to move its bytes at the chip's HBM bandwidth over the
kernel's device time. Per round it reads K padded winner rows and the
old global and writes the new global: (K + 2) * P * 4 bytes, P the
parameter count (the merge is padded to the cell's K, the engine's
``k_max``). The kernel is the Mosaic custom call in the merge
program (``sweep_merge``). Bandwidth bounds it: its operations are two
per element read."""


def read(v):
    ops = [o for o in v.trace.ops if o.module == "jit_sweep_merge"
           and o.opcode == "custom-call" and "tpu_custom_call" in o.hlo]
    if not ops:
        return None
    secs = sum(o.dur_ns for o in ops) * 1e-9 / len(v.trace.devices)
    nbytes = (v.cell.params["k"] + 2) * v.parameters * 4 * v.rounds
    return 100.0 * nbytes / v.peak["hbm_bytes_per_s"] / secs
