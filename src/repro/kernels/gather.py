"""Pallas TPU kernel: gather-K combine for winner-sparse merges.

    out = any(w != 0) ? sum_j w_j * stacked[idx_j] : glob

The winner-sparse Eq. 1 (DESIGN.md §9): instead of a masked reduction
over the full (U, ...) cohort stack, gather the K winner rows straight
out of HBM — the scalar-prefetched index vector drives the row block's
``index_map``, so the DMA engine reads only the K selected rows, never
the other U−K — and reduce over the compact K axis. The grid iterates
(column block, winner) with the winner axis fastest: the output tile
stays resident while the K gathered tiles accumulate into it in f32.

The same op serves the dense fused merge (idx = winner ids into the
(U, ...) trained stack) and the sparse round path (idx = positions into
the already-compact (K_max, ...) stack); the reduce sees identical
(K, BLOCK) values either way, which is what makes the two paths
bit-identical (tests/test_sparse.py).

Masked semantics match ``kernels.fedavg``: a zero weight (padding or a
masked candidate) contributes EXACT zero even when its row is
non-finite, and an all-zero weight vector returns ``glob`` unchanged —
the winnerless-round guard lives in-op so vmapped sweep lanes get it
per-lane.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fedavg import BLOCK_COLS, _retile


def _kernel(idx_ref, x_ref, w_ref, g_ref, o_ref):
    del idx_ref                        # consumed by the block index_map
    j = pl.program_id(1)
    w = w_ref[j, 0]
    row = x_ref[...].astype(jnp.float32)          # (1, BLOCK_COLS)
    term = jnp.where(w != 0.0, row * w, 0.0)

    @pl.when(j == 0)
    def _():
        o_ref[...] = term

    @pl.when(j > 0)
    def _():
        o_ref[...] = o_ref[...] + term

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        has = jnp.any(w_ref[...] != 0.0)
        o_ref[...] = jnp.where(has, o_ref[...],
                               g_ref[...].astype(jnp.float32))


def gather_combine_pallas(stacked, idx, weights, glob, *,
                          interpret=False):
    """stacked: (S, ...) any shape; idx: (K,) int32 row indices;
    weights: (K,) f32; glob: stacked.shape[1:]."""
    s = stacked.shape[0]
    k = idx.shape[0]
    orig_shape = stacked.shape[1:]
    n = 1
    for d in orig_shape:
        n *= d
    x = _retile(stacked, s)                       # (S, cols)
    cols = x.shape[1]
    # (S, 1, cols) with the row axis squeezed out of the block: the
    # block's last two dims (1, BLOCK_COLS) then span the array's full
    # middle dim, which the TPU tiling accepts for any S — a (1, BLOCK)
    # block over (S, cols) is not 8-row aligned and does not compile
    x = x.reshape(s, 1, cols)
    g = _retile(glob[None], 1)                    # (1, cols), same padding
    w = weights.reshape(k, 1).astype(jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(cols // BLOCK_COLS, k),
        in_specs=[
            pl.BlockSpec((pl.Squeezed(), 1, BLOCK_COLS),
                         lambda i, j, idx_ref: (idx_ref[j], 0, i)),
            pl.BlockSpec((k, 1), lambda i, j, idx_ref: (0, 0)),
            pl.BlockSpec((1, BLOCK_COLS), lambda i, j, idx_ref: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_COLS),
                               lambda i, j, idx_ref: (0, i)),
    )
    out = pl.pallas_call(
        _kernel,
        name="gather_combine",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, cols), jnp.float32),
        interpret=interpret,
    )(idx.astype(jnp.int32), x, w, g)
    return out.reshape(cols)[:n].reshape(orig_shape).astype(stacked.dtype)
