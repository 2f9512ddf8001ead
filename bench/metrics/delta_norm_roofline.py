"""Roofline share of the Eq. 2 reduction kernel (``kernels/delta_norm.py``):
the least time to read its bytes at the chip's HBM bandwidth over the
kernel's device time. Per round it reads every trained row and the
global once: (rows + 1) * P * 4 bytes, rows = U on fused rounds and
k_max on winner-sparse ones. The kernel is the Mosaic custom call of
the training program whose result is a pair of sums (the SGD update
kernel returns one array). Bandwidth bounds it: three operations per
element read."""


def read(v):
    ops = [o for o in v.trace.ops
           if o.module in ("jit_sweep_round", "jit_round_fn")
           and o.opcode == "custom-call" and "tpu_custom_call" in o.hlo
           and o.hlo.split("=", 1)[1].lstrip().startswith("(")]
    if not ops:
        return None
    tr = v.cell.params
    rows = tr["k"] if tr["round_mode"] == "sparse" else tr["users"]
    secs = sum(o.dur_ns for o in ops) * 1e-9 / len(v.trace.devices)
    nbytes = (rows + 1) * v.parameters * 4 * v.rounds
    return 100.0 * nbytes / v.peak["hbm_bytes_per_s"] / secs
