"""Pallas TPU kernel: robust FedAvg combine (fault layer, DESIGN.md §8)

    out = sum_k w_k * (s_k == 1 ? x_k : g + s_k * (x_k - g))

The fault layer's guarded Eq. 1: per-row shrink factors ``s_k`` apply
the delta-norm clip / corruption factor in delta space against the old
global ``g`` before the same masked K-way weighted reduction as
``kernels.fedavg``. Tiling is identical to ``fedavg_pallas`` — each
grid step loads one (K, BLOCK) tile of the stack plus the matching
(1, BLOCK) tile of the global — so the kernel stays at the streaming
lower bound (K+1 reads, 1 write per output block).

Exactness: ``s_k == 1`` rows take a bit-level passthrough (no
arithmetic), zero-weight rows contribute exact zero even when
non-finite — with all-ones scales this is bit-for-bit
``fedavg_pallas`` (parity-tested in tests/test_faults.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fedavg import BLOCK_COLS, _retile


def _kernel(x_ref, w_ref, s_ref, g_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # (K, 1, BLOCK_COLS)
    w = w_ref[...].astype(jnp.float32)          # (K, 1)
    s = s_ref[...].astype(jnp.float32)          # (K, 1)
    g = g_ref[...].astype(jnp.float32)          # (1, BLOCK_COLS)
    sw = s[:, :, None]
    shrunk = jnp.where(sw == 1.0, x, g[None] + sw * (x - g[None]))
    ww = w[:, :, None]
    # masked semantics: weight == 0 contributes exact zero even for a
    # non-finite (quarantined / corrupted) row
    terms = jnp.where(ww != 0.0, shrunk * ww, 0.0)
    o_ref[...] = jnp.sum(terms, axis=0).astype(o_ref.dtype)


def robust_pallas(stacked, weights, scales, global_ref, *,
                  interpret=False):
    """stacked: (K, ...) any shape; weights/scales: (K,) f32;
    global_ref: stacked.shape[1:]."""
    k = stacked.shape[0]
    orig_shape = stacked.shape[1:]
    n = 1
    for sdim in orig_shape:
        n *= sdim
    x = _retile(stacked, k)                      # (K, cols)
    cols = x.shape[1]
    x = x.reshape(k, 1, cols)
    g = _retile(global_ref[None], 1)             # (1, cols), same padding
    w = weights.reshape(k, 1).astype(jnp.float32)
    s = scales.reshape(k, 1).astype(jnp.float32)
    grid = (cols // BLOCK_COLS,)
    out = pl.pallas_call(
        _kernel,
        name="robust_combine",
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, 1, BLOCK_COLS), lambda i: (0, 0, i)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, BLOCK_COLS), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_COLS), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, cols), stacked.dtype),
        interpret=interpret,
    )(x, w, s, g)
    return out.reshape(cols)[:n].reshape(orig_shape)
