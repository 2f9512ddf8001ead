"""Pallas TPU kernel: fused ||w_k - w||^2 and ||w||^2 in one HBM pass.

Eq. 2's distance needs, per layer, both the delta norm and the reference
norm. Naive jnp lowers to: read w_k, read w, write (w_k - w), read it
back for the square-reduce, plus a second pass for ||w||^2 — ~5 HBM
touches. This kernel streams both operands through VMEM once and keeps
the two f32 accumulators in (1, 1) outputs held whole in SMEM, the
scalar memory (a scalar store to VMEM does not compile for the chip):
2 reads total, which matters when w is a terabyte-scale model
(DESIGN.md §3).

Grid: 1-D over row-blocks of the flattened-and-(8,128)-retiled operand.
TPU grid steps execute sequentially on a core, so accumulating into the
output ref across steps is well-defined.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 256  # (256, 128) f32 tile = 128 KiB VMEM per operand
LANES = 128


def _kernel(wl_ref, wg_ref, d2_ref, g2_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        d2_ref[0, 0] = jnp.float32(0.0)
        g2_ref[0, 0] = jnp.float32(0.0)

    wl = wl_ref[...].astype(jnp.float32)
    wg = wg_ref[...].astype(jnp.float32)
    d = wl - wg
    d2_ref[0, 0] += jnp.sum(d * d)
    g2_ref[0, 0] += jnp.sum(wg * wg)


def _retile(x):
    """Flatten + zero-pad to (rows, 128) with rows % BLOCK_ROWS == 0.
    Zero padding is exact for both accumulated quantities."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    rows = -(-n // LANES)
    rows = -(-rows // BLOCK_ROWS) * BLOCK_ROWS
    padded = jnp.zeros((rows * LANES,), x.dtype).at[:n].set(flat)
    return padded.reshape(rows, LANES)


def delta_norm_pallas(w_local, w_global, *, interpret=False):
    wl = _retile(w_local)
    wg = _retile(w_global)
    rows = wl.shape[0]
    grid = (rows // BLOCK_ROWS,)
    d2, g2 = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(wl, wg)
    return d2[0, 0], g2[0, 0]
